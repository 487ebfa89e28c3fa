"""Lane-padded resident rows: the IVF-PQ scan cache and the refine rows.

Row arrays whose width is not a multiple of the TPU's 128 lanes are stored
with zero lanes up to the next multiple (``_common.lane_pad``), so that the
search programs read them in their stored layout.  These tests hold the
padded arrays to the results of the unpadded ones, on every path that
writes or reads them.  Corpus dim 96 with a distinct row count keeps the
warmed-service shapes apart from the other serving suites.
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import serve
from raft_tpu.neighbors import ivf_pq, refine
from raft_tpu.neighbors._common import lane_pad, padded_width
from raft_tpu.store import paginate_index

refine_mod = importlib.import_module("raft_tpu.neighbors.refine")

N, Q, K = 1500, 8, 10


def _rows(d: int, seed: int = 0):
    rng = np.random.default_rng(seed + d)
    centers = rng.standard_normal((12, d)).astype(np.float32) * 3.0
    x = centers[rng.integers(0, 12, N)] + rng.standard_normal((N, d))
    q = centers[rng.integers(0, 12, Q)] + rng.standard_normal((Q, d))
    return x.astype(np.float32), q.astype(np.float32)


#: pq_dim per width so rot_dim == d (96 and 100 unaligned, 128 aligned)
_PQ_DIM = {96: 48, 100: 25, 128: 64}
_BUILT = {}


def _built(d: int, metric: str, dtype: str):
    key = (d, metric, dtype)
    if key not in _BUILT:
        x, q = _rows(d)
        params = ivf_pq.IndexParams(
            n_lists=12, metric=metric, pq_dim=_PQ_DIM[d], pq_bits=6,
            kmeans_n_iters=3, decoded_dtype=dtype,
        )
        _BUILT[key] = (ivf_pq.build(params, x), x, q)
    return _BUILT[key]


def _unpadded(index):
    """The same index with its scan cache cut back to rot_dim lanes."""
    return ivf_pq.Index(
        index.metric, index.codebook_kind, index.pq_bits, index.centers,
        index.centers_rot, index.rotation, index.codebook, index.list_codes,
        index.list_index, index.list_sizes,
        index.list_data[..., : index.rot_dim], index.list_y2,
        index.scan_scale, headroom=index.headroom,
    )


def _assert_same(a, b):
    """Same ids; distances equal up to the order of f32 summation, which
    a wider contraction may change on the CPU (n·eps over ~100 terms)."""
    (va, ia), (vb, ib) = a, b
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    np.testing.assert_allclose(
        np.asarray(va), np.asarray(vb), rtol=1e-5, atol=1e-6
    )


def _assert_identical(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("d", [96, 100, 128])
def test_lane_pad_helper(d):
    x = np.arange(3 * d, dtype=np.float32).reshape(3, d)
    w = padded_width(d)
    assert w % 128 == 0 and d <= w < d + 128
    out = lane_pad(x)
    if d == w:
        assert out is x  # aligned widths keep their path exactly
    else:
        assert out.shape == (3, w)
        np.testing.assert_array_equal(np.asarray(out)[:, :d], x)
        assert not np.asarray(out)[:, d:].any()
    assert lane_pad(x, d + 5).shape == (3, d + 5)


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("d", [96, 100, 128])
def test_cache_stored_at_padded_width(d, dtype):
    index, _, _ = _built(d, "sqeuclidean", dtype)
    assert index.rot_dim == d
    assert index.list_data.shape == (
        index.n_lists, index.list_cap, padded_width(d)
    )
    assert not np.asarray(index.list_data[..., d:]).any()
    if d == 128:
        assert index.list_data.shape[2] == 128


@pytest.mark.parametrize("strategy", ["query_major", "probe_major"])
@pytest.mark.parametrize("leg", ["xla", "pallas"])
@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("metric", ["inner_product", "sqeuclidean"])
@pytest.mark.parametrize("d", [96, 100, 128])
def test_padded_search_matches_unpadded(
    monkeypatch, d, metric, dtype, leg, strategy
):
    """Same ids, distances within 1e-6, on the XLA legs and the fused
    Pallas legs (interpret mode here)."""
    monkeypatch.setenv("RAFT_TPU_PALLAS", "1" if leg == "pallas" else "0")
    index, _, q = _built(d, metric, dtype)
    sp = ivf_pq.SearchParams(n_probes=6, strategy=strategy)
    _assert_same(
        ivf_pq.search(sp, index, q, K),
        ivf_pq.search(sp, _unpadded(index), q, K),
    )


@pytest.mark.parametrize("d", [96, 128])
def test_extend_keeps_padded_width(d):
    x, q = _rows(d)
    params = ivf_pq.IndexParams(
        n_lists=12, pq_dim=_PQ_DIM[d], pq_bits=6, kmeans_n_iters=3,
    )
    head = ivf_pq.build(params, x[:1000])
    # the in-place append, then a repack past the lists' spare capacity
    fast = ivf_pq.extend(head, x[1000:1100])
    grown = ivf_pq.extend(fast, np.concatenate([x[1100:]] * 3))
    sp = ivf_pq.SearchParams(n_probes=6)
    for index in (fast, grown):
        assert index.list_data.shape[2] == padded_width(d)
        assert not np.asarray(index.list_data[..., d:]).any()
        _assert_same(
            ivf_pq.search(sp, index, q, K),
            ivf_pq.search(sp, _unpadded(index), q, K),
        )


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_save_load_rebuilds_padded_cache(tmp_path, dtype):
    index, _, q = _built(96, "sqeuclidean", dtype)
    path = str(tmp_path / "pq")
    ivf_pq.save(path, index)
    back = ivf_pq.load(path)
    assert back.list_data.shape == index.list_data.shape
    np.testing.assert_array_equal(
        np.asarray(back.list_data), np.asarray(index.list_data)
    )
    sp = ivf_pq.SearchParams(n_probes=6)
    _assert_same(ivf_pq.search(sp, back, q, K), ivf_pq.search(sp, index, q, K))


def test_paged_view_keeps_padded_width():
    x, q = _rows(96)
    params = ivf_pq.IndexParams(
        n_lists=12, pq_dim=48, pq_bits=6, kmeans_n_iters=3,
    )
    mono = ivf_pq.build(params, x)
    paged = ivf_pq.build(params, x)
    tiered = paginate_index(paged, page_rows=64, budget=None, name="lanes")
    assert paged.list_data.shape[2] == padded_width(96)
    assert tiered.store.data.shape[-1] == padded_width(96)
    sp = ivf_pq.SearchParams(n_probes=6)
    _assert_same(ivf_pq.search(sp, paged, q, K), ivf_pq.search(sp, mono, q, K))


@pytest.mark.parametrize("host", [False, True])
@pytest.mark.parametrize(
    "metric", ["sqeuclidean", "euclidean", "inner_product", "cosine"]
)
def test_refine_over_prepared_rows(metric, host):
    x, q = _rows(96)
    rows = refine_mod.prepare_rows(x)
    assert rows.shape == (N, padded_width(96))
    cand = np.random.default_rng(1).integers(-1, N, (Q, 40)).astype(np.int32)
    _assert_identical(
        refine(rows, q, cand, K, metric=metric, host=host),
        refine(x, q, cand, K, metric=metric, host=host),
    )


def test_refine_rejects_rows_narrower_than_queries():
    x, q = _rows(96)
    cand = np.zeros((Q, 20), np.int32)
    with pytest.raises(ValueError, match="wide"):
        refine(x[:, :64], q, cand, K)


def test_prepare_rows_keeps_aligned_rows():
    x, _ = _rows(128)
    xd = jnp.asarray(x)
    assert refine_mod.prepare_rows(xd) is xd


def _mutable(d: int):
    index, x, _ = _built(d, "sqeuclidean", "bfloat16")
    return serve.MutableIndex(
        index, search_params=ivf_pq.SearchParams(n_probes=6),
        refine_dataset=jnp.asarray(x),
    ), x


@pytest.mark.parametrize("d", [96, 128])
def test_mutable_index_refine_rows(d):
    mi, x = _mutable(d)
    assert mi.refine_rows.shape == (N, padded_width(d))
    assert mi.refine_dataset.shape == (N, d)
    np.testing.assert_array_equal(np.asarray(mi.refine_dataset), x)
    # the shape check is of the logical rows: padded rows are refused too
    for wrong in (x[1:], np.zeros((N, d + 128), np.float32)):
        with pytest.raises(ValueError, match="refine_dataset"):
            serve.MutableIndex(
                mi.index, search_params=mi.search_params,
                refine_dataset=wrong,
            )
    index = mi.index
    cache_pad = (
        index.n_lists * index.list_cap * (padded_width(d) - d) * 2
    )
    assert mi.lane_pad_bytes() == cache_pad + N * (padded_width(d) - d) * 4
    if d == 128:
        assert mi.lane_pad_bytes() == 0
    # device bytes count the arrays as they are held
    assert mi.device_bytes() >= int(mi.refine_rows.nbytes) + int(
        index.list_data.nbytes
    )


def test_mutable_refined_search_matches_unpadded_refine():
    mi, x = _mutable(96)
    _, _, q = _built(96, "sqeuclidean", "bfloat16")
    _, cand = ivf_pq.search(mi.search_params, mi.index, q, K * 4)
    _assert_identical(mi.search(q, K), refine(x, q, cand, K))


def test_save_load_and_compaction_keep_refine_rows(tmp_path):
    mi, x = _mutable(96)
    _, _, q = _built(96, "sqeuclidean", "bfloat16")
    path = str(tmp_path / "mi")
    mi.save(path)
    back = serve.MutableIndex.load(path)
    assert back.refine_rows.shape == (N, padded_width(96))
    np.testing.assert_array_equal(np.asarray(back.refine_dataset), x)
    _assert_identical(back.search(q, K), mi.search(q, K))

    # compactor round trip: the shadow takes its rows from the refine rows
    from raft_tpu.serve.compactor import CompactionPolicy, Compactor

    svc = serve.SearchService(k=K, max_batch=4, max_delay_ms=0.5,
                              compaction=False)
    try:
        svc.add_index("lanes", back, warmup=False)
        back.delete(np.arange(0, 30))
        comp = Compactor(svc, CompactionPolicy(
            chunk_rows=256, gate_queries=8, max_side_rows=16,
        ), start=False)
        res = comp.trigger_now("lanes")
        assert res["status"] == "promoted", res
        served = svc.get("lanes")
        assert served.refine_rows.shape[1] == padded_width(96)
        assert served.refine_dataset.shape == (served.main_size, 96)
        np.testing.assert_array_equal(
            np.asarray(served.refine_dataset)[: N - 30], x[30:]
        )
        assert svc.stats("lanes")["lane_pad_bytes"] == served.lane_pad_bytes()
        assert served.lane_pad_bytes() > 0
        _, ids = served.search(q, K)
        ids = np.asarray(ids)
        assert ((ids >= 30) | (ids == -1)).all()
    finally:
        svc.stop()
