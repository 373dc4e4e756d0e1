"""The open-loop schedule gives every seed the same work: the same number
of requests and the same sizes, in another order and at other times."""

import numpy as np
import pytest

from bench.tests import tiny
from bench.traffic import poisson


@pytest.mark.parametrize("seconds", [0.5, 30.0])
def test_schedule_is_the_same_work_for_every_seed(seconds):
    c = tiny.cell("epsilon.serve")
    pool = c.params["pool_rows"]
    seen = []
    for seed in (1, 2**33 + 7, 2**31 + 5):
        c.seed = seed
        due, sizes, offsets = poisson.schedule(c, seconds, pool)
        assert due.size == round(c.params["rate_per_s"] * seconds)
        assert (np.diff(due) >= 0).all()
        assert 0 <= due[0] and due[-1] < seconds
        assert ((offsets >= 0) & (offsets + sizes <= pool)).all()
        seen.append((sizes, due))
    counts = [np.unique(s, return_counts=True)[1].tolist() for s, _ in seen]
    assert counts[0] == counts[1] == counts[2]
    assert not np.array_equal(seen[0][1], seen[1][1])
