"""Test configuration: run on CPU with 8 virtual devices.

Mirrors the reference's strategy of simulating multi-node with
multi-process-per-box (SURVEY §4, raft-dask LocalCUDACluster tests): here a
single process gets 8 XLA host devices so mesh/sharding/collective logic is
exercised without TPU hardware.

The platform is switched through jax.config, which works because no
backend has been initialized yet when pytest loads this file.
"""

import os
import sys

import jax

# the chip runs chip_smoke.py, never this suite: tests that need the TPU's
# compiler describe a chip (tests/test_tpu_compile.py)
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Slow (10^5-row scale) tests run only when explicitly requested —
    locally via RAFT_TPU_RUN_SLOW=1, or in the TPU bench environment
    (mirrors the reference's split between unit suites and the large
    ann-bench datasets)."""
    if os.environ.get("RAFT_TPU_RUN_SLOW"):
        return
    skip = pytest.mark.skip(reason="slow scale test; set RAFT_TPU_RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _reset_obs_globals(monkeypatch, tmp_path):
    """Isolate per-test observability state.

    The flight recorder, health transition edge, recent-span ring and
    histogram exemplars are process-wide by design; without a reset a
    test's incident dump (or a leftover UNHEALTHY verdict) leaks into the
    next test's assertions.  Auto-dumps are pointed at the test's tmp dir
    so nothing lands in the real RAFT_TPU_FLIGHT_DIR / system temp.
    Counters/gauges/histogram *counts* are deliberately left alone — the
    existing suites assert on monotonic totals.
    """
    from raft_tpu.obs import events, flight, health, spans
    from raft_tpu.obs.registry import default_registry

    monkeypatch.setenv("RAFT_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    events.reset()  # drops the default bus + incident manager + debounce
    flight.reset()
    health.reset_transitions()

    # query archive + tail sampler (lazy: only if imported — the reset
    # also re-reads the RAFT_TPU_EXPLAIN_* knobs a test may have set)
    def _reset_explain():
        explain_mod = sys.modules.get("raft_tpu.obs.explain")
        if explain_mod is not None:
            explain_mod.reset()

    _reset_explain()
    yield
    events.reset()
    flight.reset()
    health.reset_transitions()
    _reset_explain()
    spans.clear_recent()
    spans.set_ring_capacity()
    default_registry().clear_exemplars()
    # stop any compactor workers a test left running (lazy: only if the
    # module was imported — most tests never touch it)
    compactor_mod = sys.modules.get("raft_tpu.serve.compactor")
    if compactor_mod is not None:
        compactor_mod.reset()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)
