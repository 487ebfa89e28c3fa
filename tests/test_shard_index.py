"""Index sharding (raft_tpu.serve.shard): sharded search over the forced
8-device host mesh must match the single-device backend — exact ids for
brute_force/ivf_flat (exhaustive probing), recall-equivalent for ivf_pq
and for the bf16 merge knob — plus registry/service integration: register
and hot-swap sharded versions under concurrent readers, ReplicaGroup's
``shard_index=`` mode, the pre-sharded-query device_put skip, tombstone
folding, and the per-shard capacity/obs accounting."""

import threading

import numpy as np
import pytest

import jax

from raft_tpu import obs, serve
from raft_tpu.neighbors import brute_force, ivf_flat, ivf_pq
from raft_tpu.serve.shard import ShardedIndex, merge_dtype_from_env
from raft_tpu.stats import recall_at_k

KINDS = ("brute_force", "ivf_flat", "ivf_pq")


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    x = rng.random((600, 24), dtype=np.float32)
    q = rng.random((16, 24), dtype=np.float32)
    return x, q


def _build(kind: str, x: np.ndarray):
    """(built index, search params) with near-exhaustive probing so the
    per-shard probed set equals the global one and results are exact."""
    if kind == "brute_force":
        return brute_force.build(x), None
    if kind == "ivf_flat":
        idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
        return idx, ivf_flat.SearchParams(n_probes=16)
    idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=24, pq_bits=8), x)
    return idx, ivf_pq.SearchParams(n_probes=16)


def _reference(kind, index, params, q, k):
    if kind == "brute_force":
        return brute_force.knn(index.dataset, q, k, metric=index.metric)
    mod = ivf_flat if kind == "ivf_flat" else ivf_pq
    return mod.search(params, index, q, k)


# ---------------------------------------------------------------------------
# sharded == single-device


@pytest.mark.parametrize("kind", KINDS)
def test_sharded_matches_single_device(corpus, kind):
    x, q = corpus
    k = 10
    index, params = _build(kind, x)
    vref, iref = _reference(kind, index, params, q, k)
    sh = ShardedIndex.from_index(index, search_params=params, merge_dtype=None)
    assert sh.n_shards == len(jax.devices())
    v, i = sh.search(q, k)
    if kind in ("brute_force", "ivf_flat"):
        np.testing.assert_array_equal(np.asarray(i), np.asarray(iref))
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(vref), rtol=1e-4, atol=1e-4
        )
    else:
        # PQ distances are approximations; exhaustive probing still makes
        # the sharded candidate set a superset, so id recall must be ~1
        assert recall_at_k(np.asarray(i), np.asarray(iref)) >= 0.99


def test_sharded_bf16_merge_recall(corpus):
    x, q = corpus
    k = 10
    index, params = _build("ivf_flat", x)
    _, iref = _reference("ivf_flat", index, params, q, k)
    sh = ShardedIndex.from_index(
        index, search_params=params, merge_dtype=jax.numpy.bfloat16
    )
    _, i = sh.search(q, k)
    # the quantized merge may reorder near-ties but must not lose
    # neighbors wholesale
    assert recall_at_k(np.asarray(i), np.asarray(iref)) >= 0.95


def test_merge_dtype_env(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_SHARD_MERGE_DTYPE", "bfloat16")
    assert merge_dtype_from_env() is jax.numpy.bfloat16
    monkeypatch.setenv("RAFT_TPU_SHARD_MERGE_DTYPE", "float32")
    assert merge_dtype_from_env() is None
    monkeypatch.setenv("RAFT_TPU_SHARD_MERGE_DTYPE", "int4")
    with pytest.raises(ValueError, match="RAFT_TPU_SHARD_MERGE_DTYPE"):
        merge_dtype_from_env()


def test_sharded_folds_tombstones(corpus):
    x, q = corpus
    k = 5
    mi = serve.MutableIndex(brute_force.build(x))
    mi.delete(np.arange(100))
    sh = ShardedIndex.from_index(mi, merge_dtype=None)
    assert sh.size == len(x) - 100
    v, i = sh.search(q, k)
    i = np.asarray(i)
    assert (i >= 100).all()
    vref, iref = brute_force.knn(x[100:], q, k, metric="sqeuclidean")
    np.testing.assert_array_equal(i - 100, np.asarray(iref))


def test_sharding_rejects_live_side_buffer(corpus):
    x, _ = corpus
    mi = serve.MutableIndex(brute_force.build(x))
    mi.upsert(np.random.default_rng(0).random((4, x.shape[1]), np.float32))
    with pytest.raises(ValueError, match="side-buffer"):
        ShardedIndex.from_index(mi)


# ---------------------------------------------------------------------------
# capacity + obs accounting


def test_per_shard_bytes_shrink(corpus):
    x, _ = corpus
    index, params = _build("ivf_flat", x)
    sh = ShardedIndex.from_index(index, search_params=params, label="cap")
    n_dev = sh.n_shards
    full = sum(
        int(np.asarray(a).nbytes)
        for a in (index.centers, index.list_data, index.list_index,
                  index.list_sizes, index.list_norms)
    )
    per_dev = sh.per_shard_bytes()[0]
    # list payloads split ~1/N; only the (small) centers stack replicates,
    # so the per-device footprint must shrink by a large fraction of N
    assert per_dev < full / (n_dev / 2)
    # per-shard gauges landed in the process registry, one series per shard
    snap = obs.default_registry().snapshot()
    rows = snap["gauges"].get("raft_tpu_shard_rows", {})
    series = [s for s in rows if "index=cap" in s]
    assert len(series) == n_dev
    lists = snap["gauges"].get("raft_tpu_shard_lists", {})
    assert sum(v for s, v in lists.items() if "index=cap" in s) == 16


# ---------------------------------------------------------------------------
# serve integration: registry / service / replicas


def test_registry_accepts_and_swaps_sharded(corpus):
    x, q = corpus
    index, params = _build("ivf_flat", x)
    reg = serve.IndexRegistry()
    sh = ShardedIndex.from_index(index, search_params=params)
    assert reg.register("s", sh) == 1
    assert reg.get("s") is sh
    sh2 = ShardedIndex.from_index(index, search_params=params)
    assert reg.swap("s", sh2) == 2
    assert reg.get("s") is sh2
    with pytest.raises(TypeError, match="ShardedIndex"):
        reg.register("raw", object())


def test_replica_group_shard_index_mode(corpus):
    x, q = corpus
    k = 7
    index, params = _build("ivf_flat", x)
    vref, iref = _reference("ivf_flat", index, params, q, k)
    reg = serve.IndexRegistry()
    reg.register(
        "m", serve.MutableIndex(index, search_params=params)
    )
    group = serve.ReplicaGroup(reg, shard_index=True)
    v, i = group.search("m", q, k)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(iref))
    # an already-sharded registry entry dispatches directly in either mode
    reg2 = serve.IndexRegistry()
    reg2.register("s", ShardedIndex.from_index(index, search_params=params))
    v2, i2 = serve.ReplicaGroup(reg2, shard_index=False).search("s", q, k)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(iref))


def test_service_hot_swap_sharded_under_concurrent_readers(corpus):
    x, q = corpus
    k = 5
    index, params = _build("ivf_flat", x)
    sh = ShardedIndex.from_index(index, search_params=params)
    svc = serve.SearchService(k=k, max_batch=8, max_delay_ms=0.2)
    try:
        svc.add_index("hot", sh, warmup=False)
        assert svc.get("hot") is sh
        stop = threading.Event()
        errors = []

        def reader():
            j = 0
            while not stop.is_set():
                try:
                    d, ids = svc.search("hot", q[j % len(q)], timeout=60)
                    assert ids.shape == (k,)
                    assert (np.asarray(ids) >= 0).all()
                except Exception as e:  # noqa: BLE001 — collected for assert
                    errors.append(e)
                    return
                j += 1

        threads = [threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        # swap in freshly re-sharded versions while readers hammer away
        for _ in range(3):
            svc.swap(
                "hot", ShardedIndex.from_index(index, search_params=params)
            )
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors[0]
        assert svc.registry.version("hot") == 4
        st = svc.stats("hot")
        assert st["kind"] == "ivf_flat" and st["size"] == len(x)
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# satellite: replicated search skips device_put for pre-sharded queries


def test_replicated_search_skips_device_put_when_pre_sharded(corpus):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu.comms.comms import local_comms
    from raft_tpu.serve.replica import make_replicated_search

    x, q = corpus
    k = 5
    index = brute_force.build(x)
    comms = local_comms()
    run = make_replicated_search(
        comms, lambda qs, kk: brute_force.knn(x, qs, kk, metric=index.metric)
    )
    size = comms.get_size()
    n_rows = (len(q) // size) * size
    staged = jax.device_put(
        jax.numpy.asarray(q[:n_rows]),
        NamedSharding(comms.mesh, P(comms.axis, None)),
    )
    vref, iref = run(np.asarray(q[:n_rows]), k)  # warm the executable

    calls = []
    real_put = jax.device_put

    def counting_put(*args, **kwargs):
        calls.append(1)
        return real_put(*args, **kwargs)

    jax.device_put = counting_put
    try:
        v, i = run(staged, k)
        assert not calls, "pre-sharded queries still paid a device_put"
        v2, i2 = run(np.asarray(q[:n_rows]), k)
        assert calls, "host queries must still be staged"
    finally:
        jax.device_put = real_put
    np.testing.assert_array_equal(np.asarray(i), np.asarray(iref))
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(iref))


def test_shard_skew_probe_publishes_gauges():
    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    rng = np.random.default_rng(5)
    x = rng.standard_normal((512, 16), dtype=np.float32)
    q = rng.standard_normal((8, 16), dtype=np.float32)
    sh = ShardedIndex.from_index(brute_force.build(x))
    out = sh.measure_shard_skew(q, k=4)
    assert len(out["per_shard_s"]) == sh.n_shards
    assert all(t > 0.0 for t in out["per_shard_s"])
    assert out["skew"] >= 1.0
    prom = obs.to_prometheus()
    assert "raft_tpu_shard_device_seconds" in prom
    assert "raft_tpu_shard_device_skew" in prom
