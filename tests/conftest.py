import os
import sys

# tests see ONE CPU device (the dry-run sets its own 512-device flag in a
# separate process); repo root on path so `benchmarks` imports resolve.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# Shared jaxpr-walking helpers.
#
# The canonical versions moved into ``repro.analysis.jaxprs`` in PR 6 (the
# analyzer's jaxpr plane is built on them); these re-exports keep the
# long-standing `from conftest import walk_eqns` sites working and
# guarantee tests and analyzer can never drift apart.
# ---------------------------------------------------------------------------

from repro.analysis.jaxprs import (  # noqa: E402,F401
    dense_operand_intermediates, walk_eqns)


# ---------------------------------------------------------------------------
# Opt-in invariant lane: `pytest --repro-debug` sets REPRO_DEBUG=1 for the
# whole session, so every DsArray construction (and the sparse BCOO paths)
# re-validates `check_invariants()` — the CI debug lane runs the full
# tier-1 suite this way, and failures name the offending block coordinates.
# ---------------------------------------------------------------------------

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--repro-debug", action="store_true", default=False,
        help="run with REPRO_DEBUG=1: validate DsArray.check_invariants() "
             "at every construction")


def pytest_configure(config):
    if config.getoption("--repro-debug"):
        os.environ["REPRO_DEBUG"] = "1"


@pytest.fixture(autouse=True)
def _repro_debug_invariants(request):
    """Keep REPRO_DEBUG visible per-test when the lane is armed (tests that
    themselves mutate the env restore it afterwards)."""
    if request.config.getoption("--repro-debug"):
        prev = os.environ.get("REPRO_DEBUG")
        os.environ["REPRO_DEBUG"] = "1"
        yield
        if prev is None:
            os.environ["REPRO_DEBUG"] = "1"
        else:
            os.environ["REPRO_DEBUG"] = prev
    else:
        yield


# ---------------------------------------------------------------------------
# Telemetry hygiene: every test starts with zeroed counters and an empty
# trace buffer.  One obs.reset_all() replaces the per-module autouse
# fixtures that used to hand-reset serve/resilience stats in their own
# test files (plan compiled caches are storage, not telemetry — tests that
# need a cold cache still call plan.clear_cache() themselves).
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _obs_reset():
    from repro import obs
    obs.reset_all()
    yield
    obs.reset_all()
    obs.disable()
