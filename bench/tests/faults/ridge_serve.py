"""Faults under ``PredictServer``'s served predict."""

import numpy as np

from bench.tests.faults import _patch

FAULTS = ["serve_answer_altered", "serve_half_batch"]


def serve_answer_altered(mp=None):
    """Each batch's predictions shifted by a thousandth of their largest."""
    from repro.serve import batching

    def wrap(split):
        def bad(rows, sizes):
            rows = np.asarray(rows)
            return split(rows + 1e-3 * np.abs(rows).max(), sizes)
        return bad
    _patch(mp, batching, "split_rows", wrap)


def serve_half_batch(mp=None):
    """The second half of each batch's rows left out (zeroed) before the
    predict."""
    from repro.serve import batching

    def wrap(assemble):
        def bad(payloads, bucket):
            cut = [np.asarray(p).copy() for p in payloads]
            for p in cut[len(cut) // 2:]:
                p[:] = 0
            if len(cut) == 1:
                cut[0][cut[0].shape[0] // 2:] = 0
            return assemble(cut, bucket)
        return bad
    _patch(mp, batching, "assemble", wrap)
