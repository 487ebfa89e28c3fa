"""raft_tpu.serve.ragged: heterogeneous (k, filter) requests packed into
one dispatch per capacity bucket must bit-match the same requests served
individually, stay compile-free after the one-variant-per-bucket warmup
under shuffled mixes, and agree with direct backend ground truth."""

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu import serve
from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.serve.metrics import compile_count

KINDS = ("brute_force", "ivf_flat", "ivf_pq", "cagra")

K_MAX = 8


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    x = rng.random((400, 24), dtype=np.float32)
    q = rng.random((16, 24), dtype=np.float32)
    return x, q


def _build(kind: str, x: np.ndarray) -> serve.MutableIndex:
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


def _masks(n: int):
    even = np.zeros(n, bool)
    even[::2] = True
    band = np.zeros(n, bool)
    band[100:300] = True
    return even, band


def _ragged_service(mi, *, depth: int) -> serve.SearchService:
    svc = serve.SearchService(
        k=5, max_batch=16, start=False, pipeline_depth=depth,
        ragged=serve.RaggedSpec(k_max=K_MAX),
    )
    svc.add_index("t", mi)
    return svc


# mixed per-request (k, fid-slot) workload; fid slot 0 = unfiltered,
# 1 = even mask, 2 = band mask
_MIX = [(3, 0), (K_MAX, 1), (5, 2), (K_MAX, 0), (1, 1), (7, 2), (4, 0)]


# ---------------------------------------------------------------------------
# packed == individual, every backend, serial and pipelined dispatch


@pytest.mark.parametrize("depth", (1, 2))
@pytest.mark.parametrize("kind", KINDS)
def test_packed_batch_matches_individual_requests(corpus, kind, depth):
    x, q = corpus
    svc = _ragged_service(_build(kind, x), depth=depth)
    try:
        even, band = _masks(len(x))
        fids = (0, svc.register_filter("t", even),
                svc.register_filter("t", band))
        svc.warmup("t")

        reqs = [(q[i], k, fids[f]) for i, (k, f) in enumerate(_MIX)]
        futs = [svc.submit("t", qq, k=k, fid=fid) for qq, k, fid in reqs]
        c0 = compile_count()
        svc.flush("t")
        packed = [f.result(timeout=60) for f in futs]
        assert compile_count() - c0 == 0, "packed dispatch recompiled"

        # the same requests, one at a time, through the same service
        for (qq, k, fid), (d_p, i_p) in zip(reqs, packed):
            assert d_p.shape == (k,) and i_p.shape == (k,)
            fut = svc.submit("t", qq, k=k, fid=fid)
            svc.flush("t")
            d_ref, i_ref = fut.result(timeout=60)
            np.testing.assert_array_equal(i_p, i_ref)
            np.testing.assert_allclose(d_p, d_ref, rtol=1e-5, atol=1e-5)
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# ground truth: packed filtered results == direct backend search


def test_packed_matches_direct_backend_ground_truth(corpus):
    x, q = corpus
    svc = _ragged_service(_build("ivf_flat", x), depth=1)
    try:
        even, band = _masks(len(x))
        masks = {0: None, 1: even, 2: band}
        fids = (0, svc.register_filter("t", even),
                svc.register_filter("t", band))
        svc.warmup("t")

        reqs = [(q[i], k, f) for i, (k, f) in enumerate(_MIX)]
        futs = [svc.submit("t", qq, k=k, fid=fids[f])
                for qq, k, f in reqs]
        svc.flush("t")

        idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
        sp = ivf_flat.SearchParams(n_probes=16)
        for (qq, k, f), fut in zip(reqs, futs):
            bs = None if masks[f] is None else Bitset.from_mask(
                jnp.asarray(masks[f])
            )
            d_g, i_g = ivf_flat.search(sp, idx, jnp.asarray(qq[None, :]),
                                       k, sample_filter=bs)
            d_p, i_p = fut.result(timeout=60)
            np.testing.assert_array_equal(np.asarray(i_g)[0], i_p)
            np.testing.assert_allclose(np.asarray(d_g)[0], d_p,
                                       rtol=1e-5, atol=1e-5)
            if masks[f] is not None:
                allowed = set(np.flatnonzero(masks[f]).tolist())
                assert all(i in allowed for i in i_p.tolist() if i >= 0)
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# the collapsed lattice: zero recompiles under shuffled heterogeneous
# traffic, one warmup variant per bucket


def test_zero_recompiles_under_shuffled_traffic(corpus):
    x, q = corpus
    svc = _ragged_service(_build("ivf_flat", x), depth=2)
    try:
        even, band = _masks(len(x))
        fids = (0, svc.register_filter("t", even),
                svc.register_filter("t", band))
        svc.warmup("t")
        assert svc.stats("t")["recompiles"] == 0

        rng = np.random.default_rng(5)
        c0 = compile_count()
        for _ in range(6):
            n = int(rng.integers(1, 9))  # varies the bucket too
            futs = [
                svc.submit(
                    "t", q[int(rng.integers(0, len(q)))],
                    k=int(rng.integers(1, K_MAX + 1)),
                    fid=fids[int(rng.integers(0, 3))],
                )
                for _ in range(n)
            ]
            svc.flush("t")
            for f in futs:
                f.result(timeout=60)
        assert compile_count() - c0 == 0, (
            "shuffled (k, fid) traffic recompiled after warmup — a "
            "request-level degree of freedom leaked back into shape"
        )
        st = svc.stats("t")
        assert st["recompiles"] == 0
        # padding-waste / fill accounting rode along
        assert st["pad_waste_rows"] >= 0
        assert st["bucket_fill"], st
    finally:
        svc.stop()


def test_warmup_variant_count_is_per_bucket_only():
    """Classic mode warms one executable per (bucket, k, filter) variant;
    ragged warms exactly one per bucket regardless of the (k, fid) mix."""
    # dedicated shape: the process-wide jit cache must be cold for this
    # test's executables or the compile counters read 0
    rng = np.random.default_rng(23)
    x = rng.random((320, 20), dtype=np.float32)
    mi = _build("brute_force", x)
    svc = _ragged_service(mi, depth=1)
    try:
        svc.register_filter("t", _masks(len(x))[0])
        c0 = compile_count()
        svc.warmup("t")
        ragged_compiles = compile_count() - c0
    finally:
        svc.stop()

    # classic equivalent of the same heterogeneous workload: one batcher
    # variant per (k, filter) pair — 3 ks × 2 filter states here
    variants = [(k, f) for k in (1, 4, K_MAX) for f in (None, "even")]
    c0 = compile_count()
    classic = []
    try:
        for k, f in variants:
            even = _masks(len(x))[0]
            bs = Bitset.from_mask(jnp.asarray(even)) if f else None
            b = serve.MicroBatcher(
                lambda queries, _k=k, _bs=bs: mi.search(
                    queries, _k, sample_filter=_bs
                ),
                x.shape[1], max_batch=16, start=False,
            )
            b.warmup()
            classic.append(b)
        classic_compiles = compile_count() - c0
    finally:
        for b in classic:
            b.stop()
    assert ragged_compiles > 0 and classic_compiles > 0
    assert classic_compiles >= 4 * ragged_compiles, (
        f"expected ≥4x warmup-variant reduction, classic={classic_compiles} "
        f"ragged={ragged_compiles}"
    )


# ---------------------------------------------------------------------------
# filters survive compaction: after the compactor permutes the main
# structure, per-request fids must keep constraining by *global* id
# (this path used to raise NotImplementedError)


def test_filters_survive_compaction(corpus):
    from raft_tpu.core.bitset import Bitset as _Bitset
    from raft_tpu.serve.compactor import CompactionPolicy, Compactor

    x, q = corpus
    svc = _ragged_service(_build("ivf_flat", x), depth=1)
    try:
        even, band = _masks(len(x))
        fids = (0, svc.register_filter("t", even),
                svc.register_filter("t", band))
        svc.warmup("t")

        mi = svc.get("t")
        rng = np.random.default_rng(7)
        dead = np.sort(rng.choice(len(x), size=40, replace=False))
        mi.delete(dead)
        new_rows = rng.random((24, x.shape[1]), dtype=np.float32)
        new_ids = np.asarray(mi.upsert(new_rows))

        res = Compactor(
            svc,
            CompactionPolicy(chunk_rows=128, gate_queries=16,
                             max_side_rows=16),
            start=False,
        ).trigger_now("t")
        assert res["status"] == "promoted", res
        served = svc.get("t")
        assert served is not mi
        # the compacted main structure is a *permutation* of global ids —
        # the exact situation the row-space filter remap exists for
        assert served._main_ids is not None

        keep = np.setdiff1d(np.arange(len(x)), dead)
        for slot, mask in ((1, even), (2, band)):
            # ids the filter allows post-compaction: covered survivors
            # whose bit is set, plus side-born ids past the registry's
            # id space (uncovered ids are unconstrained by contract)
            allowed = np.concatenate([keep[mask[keep]], new_ids])
            allowed_rows = np.concatenate(
                [x[keep[mask[keep]]], new_rows])
            gt_local = np.asarray(
                brute_force.knn(allowed_rows, q[:4], 5)[1])
            gt = allowed[gt_local]

            futs = [svc.submit("t", q[i], k=5, fid=fids[slot])
                    for i in range(4)]
            svc.flush("t")
            for i, fut in enumerate(futs):
                _d, ids = fut.result(timeout=60)
                got = [g for g in np.asarray(ids).tolist() if g >= 0]
                assert set(got) <= set(allowed.tolist()), (
                    "filter leaked a denied (or deleted) id after "
                    "compaction"
                )
                # n_probes == n_lists: the scan is exhaustive, so the
                # filtered result must match brute force over the
                # allowed rows exactly (as a set; ties may reorder)
                assert set(got) == set(gt[i].tolist())

        # the Bitset (uniform-filter) leg of the remap, straight through
        # MutableIndex.search
        bs = _Bitset.from_mask(jnp.asarray(even))
        _d, ids = served.search(jnp.asarray(q[:2]), 5, sample_filter=bs)
        flat = [g for g in np.asarray(ids).reshape(-1).tolist() if g >= 0]
        ok = set(keep[even[keep]].tolist()) | set(new_ids.tolist())
        assert set(flat) <= ok
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# argument validation at the service boundary


def test_ragged_argument_validation(corpus):
    x, q = corpus
    svc = _ragged_service(_build("brute_force", x), depth=1)
    try:
        with pytest.raises(ValueError):
            svc.submit("t", q[0], k=K_MAX + 1)  # k beyond capacity
        with pytest.raises(ValueError):
            svc.submit("t", q[0], k=0)
        with pytest.raises(ValueError):
            svc.submit("t", q[0], fid=99)  # unregistered filter
        # default k falls back to the service k
        fut = svc.submit("t", q[0])
        svc.flush("t")
        d, i = fut.result(timeout=60)
        assert d.shape == (5,)
    finally:
        svc.stop()

    # classic services must reject the ragged-only kwargs loudly
    svc = serve.SearchService(k=3, max_batch=8, start=False)
    try:
        svc.add_index("c", _build("brute_force", x))
        with pytest.raises(ValueError):
            svc.submit("c", q[0], k=2)
        with pytest.raises(RuntimeError):
            svc.register_filter("c", _masks(len(x))[0])
    finally:
        svc.stop()


def test_ledger_reconciles_pipelined_ragged_traffic():
    # pipelined (depth 2) ragged traffic with per-request k: zero
    # post-warmup recompiles, and the stamped kernel path flows from the
    # neighbors routing code into stats() and the Prometheus export
    rng = np.random.default_rng(0)
    data = rng.standard_normal((256, 16), dtype=np.float32)
    q = rng.standard_normal((40, 16), dtype=np.float32)
    svc = serve.SearchService(k=4, max_batch=8, pipeline_depth=2,
                              ragged=serve.RaggedSpec(k_max=8, filters=False))
    svc.add_index("t", brute_force.build(data), warmup=True)
    try:
        c0 = compile_count()
        futs = [
            svc.submit("t", q[i : i + 2], k=int(1 + i % 5))
            for i in range(0, 40, 2)
        ]
        svc.flush("t")
        for f in futs:
            f.result(timeout=60)
        assert compile_count() - c0 == 0
        st = svc.stats("t")
        assert st["recompiles"] == 0
        assert st["requests"] == 20 and st["batches"] > 0
        # every dispatch is attributed: brute force has no pallas leg
        assert st["kernel_paths"] == {"xla": st["batches"]}
        # one device-stage value per dispatched batch
        assert st["stage_n"]["device"] == st["batches"]
        assert 'kernel_path="xla"' in svc.prometheus()
    finally:
        svc.stop()
