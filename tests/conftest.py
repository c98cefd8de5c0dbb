import os
import sys

# src-layout import path (tests run with PYTHONPATH=src, but be robust)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # scripts/check.sh runs `-m "not slow"` by default and the full suite in
    # --full mode; tier-1 verify (plain `pytest -x -q`) still runs everything
    config.addinivalue_line(
        "markers", "slow: long equivalence sweeps (excluded from the fast "
                   "check.sh gate; included in tier-1 and check.sh --full)")
    config.addinivalue_line(
        "markers", "spill: tests that intentionally run the block store "
                   "under a memory budget (exempt from the global "
                   "no-unexpected-spills guard)")
    config.addinivalue_line(
        "markers", "trace: tests that intentionally enable the statement "
                   "tracer (exempt from the global zero-spans guard)")


@pytest.fixture(autouse=True)
def _no_unexpected_spills(request):
    """Residency must never regress silently: with the default
    ``REPRO_MEM_BUDGET=0`` no test may cause a block spill.  Tests that
    budget the store on purpose opt out with ``@pytest.mark.spill``."""
    from repro.core.store import get_store
    st = get_store()
    before = st.stats.spills
    yield
    if request.node.get_closest_marker("spill") is None:
        from repro.core.store import get_store as _get
        cur = _get()
        after = cur.stats.spills if cur is st else 0
        assert after == before, (
            f"unexpected block-store spills during {request.node.nodeid}: "
            f"{after - before} (mark the test @pytest.mark.spill if "
            "budget-governed residency is intended)")


@pytest.fixture(autouse=True)
def _no_unexpected_spans(request):
    """The disabled path must be a true no-op: with tracing off (the test
    default) no span may be recorded anywhere in the process.  Tests that
    turn the tracer on opt out with ``@pytest.mark.trace``."""
    from repro.core import trace
    before = trace.recorded_total()
    yield
    if request.node.get_closest_marker("trace") is None:
        after = trace.recorded_total()
        assert after == before, (
            f"unexpected trace spans recorded during {request.node.nodeid}: "
            f"{after - before} (mark the test @pytest.mark.trace if tracing "
            "is intended)")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def eager_session():
    """Fresh eager-mode session per test (pandas-semantics baseline)."""
    from repro.core import EvalMode, Session, set_session
    s = set_session(Session(mode=EvalMode.EAGER, default_row_parts=3))
    yield s
    s.close()


@pytest.fixture
def lazy_session():
    from repro.core import EvalMode, Session, set_session
    s = set_session(Session(mode=EvalMode.LAZY, default_row_parts=3))
    yield s
    s.close()


@pytest.fixture
def use_pallas_kernels(monkeypatch):
    """Force the Pallas kernels (interpret mode on CPU) for this test."""
    monkeypatch.setenv("REPRO_USE_KERNELS", "1")
