"""CAGRA served: the beam search held to a plain per-query reference.

``benchmark/lib/beam_reference.py`` is the textbook single-query CAGRA
beam search in numpy float32 (no tiling, no kernel).  Given the index's
own graph and the seed ids the search drew, the program's walk must give
the same top-10 ids (except where distances tie), the same distances to
the rounding of two float32 sums of 128 terms, and expand the same number
of parents — through the XLA hop and the fused Pallas hop (interpret
mode), at search width 1 and 2, for L2 and inner product, and through a
``SearchService`` in front of a CAGRA ``MutableIndex``.

Distances are compared as a share of the pair's scale (|q|^2 + |x|^2 for
L2, |q||x| for inner product: the magnitude of the terms summed, as the
benchmark's ``dist_err`` reads it).  The two float32 sides read ~3e-7
here; a bfloat16 row gather reads ~1e-4, and must fail.
"""

from __future__ import annotations

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import kernels, serve
from raft_tpu.neighbors import cagra, ivf_flat

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, D, DEGREE, ITOPK, K = 4096, 128, 32, 64, 10
DIST_TOL = 1e-5


def _load(rel: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


beam_reference = _load("benchmark/lib/beam_reference.py", "beam_reference")


def _mixture(seed: int, n: int, q: int, unit: bool):
    """Seeded Gaussian mixture of the benchmark's shape (16 centres in
    [0, 10)^d, unit noise): base rows and queries."""
    rng = np.random.default_rng(seed)
    c = rng.uniform(0, 10, (16, D)).astype(np.float32)
    x = (c[rng.integers(0, 16, n)] + rng.normal(size=(n, D))).astype(np.float32)
    qs = (c[rng.integers(0, 16, q)] + rng.normal(size=(q, D))).astype(np.float32)
    if unit:
        x /= np.linalg.norm(x, axis=1, keepdims=True)
        qs /= np.linalg.norm(qs, axis=1, keepdims=True)
    return x, qs


@pytest.fixture(scope="module")
def built():
    """{metric: (index, base rows, queries)} at N rows, degree 32."""
    out = {}
    for seed, metric in ((25, "sqeuclidean"), (26, "inner_product")):
        x, qs = _mixture(seed, N, 48, metric == "inner_product")
        index = cagra.build(cagra.IndexParams(
            metric=metric, graph_degree=DEGREE, intermediate_graph_degree=64),
            x)
        out[metric] = (index, x, qs)
    return out


def _reference(index, queries, seeds, metric, width):
    params = cagra.SearchParams(itopk_size=ITOPK, search_width=width)
    return beam_reference.search_rows(
        np.asarray(index.dataset, np.float32), np.asarray(index.graph),
        queries, np.asarray(seeds), itopk=ITOPK, search_width=width,
        max_iterations=beam_reference.auto_max_iterations(
            ITOPK, width, index.entry_ids is not None),
        min_iterations=params.min_iterations, metric=metric, k=K)


def _gap(x, queries, dists, ids, want_d, metric):
    """Per-slot |program - reference| distance over the pair's scale."""
    rows = x[np.clip(ids, 0, None)].astype(np.float64)
    q = queries[:, None, :].astype(np.float64)
    if metric == "inner_product":
        scale = np.linalg.norm(q, axis=-1) * np.linalg.norm(rows, axis=-1)
    else:
        scale = (q * q).sum(-1) + (rows * rows).sum(-1)
    return np.abs(np.asarray(dists, np.float64) - want_d) / scale


def _assert_agrees(x, queries, dists, ids, want_d, want_i, metric):
    """Distances agree slot for slot, and each is the distance of its own
    id; so where an id differs, the two ids sit at one distance: a tie."""
    ids = np.asarray(ids)
    gap = _gap(x, queries, dists, ids, want_d, metric)
    assert gap.max() <= DIST_TOL, gap.max()
    sign = -1.0 if metric == "inner_product" else 1.0
    own = sign * np.stack([
        beam_reference._distances(x, queries[r], ids[r], metric)
        for r in range(queries.shape[0])])
    assert _gap(x, queries, dists, ids, own, metric).max() <= DIST_TOL
    differ = ids != want_i
    assert differ.sum() <= 0.01 * ids.size, np.argwhere(differ)


def _work(n_rows):
    stamp = kernels.consume_search_work()
    assert stamp is not None and stamp[1] == n_rows
    return np.asarray(stamp[0])


@pytest.mark.parametrize("metric", ["sqeuclidean", "inner_product"])
@pytest.mark.parametrize("width", [1, 2])
@pytest.mark.parametrize("hop", [
    "xla", "pallas",
    # query tiles that leave the fused hop's last block part padding
    "pallas_tile1", "pallas_tile5", "pallas_tile13", "pallas_tile64",
])
def test_walk_matches_the_beam_reference(built, metric, width, hop,
                                         monkeypatch):
    path, _, tile = hop.partition("_tile")
    monkeypatch.setenv("RAFT_TPU_PALLAS", "1" if path == "pallas" else "0")
    index, x, qs = built[metric]
    if path == "pallas":
        qs = qs[:24]  # interpret mode runs each grid step in Python
    params = cagra.SearchParams(itopk_size=ITOPK, search_width=width,
                                max_queries=int(tile or 0))
    seeds = cagra.make_seed_ids(params, index, jnp.asarray(qs), K)
    kernels.consume_kernel_path()
    d, i = cagra.search(params, index, qs, K, seed_ids=seeds)
    assert kernels.consume_kernel_path() == path
    work = _work(qs.shape[0])
    want_d, want_i, want_e = _reference(index, qs, seeds, metric, width)
    _assert_agrees(x, qs, d, i, want_d, want_i, metric)
    # the counter: each query's expanded parents, then each tile's trips
    assert np.array_equal(work[: qs.shape[0]], want_e)
    assert work[qs.shape[0]:].sum() >= want_e.max() // width


def test_bf16_row_gather_fails_the_distance_check(built):
    """Rows stored in bfloat16 (cast to f32 after the gather) read about
    1e-4 of the scale: ten times the tolerance, so the check sees it."""
    index, x, qs = built["sqeuclidean"]
    low = cagra.Index(index.metric, index.dataset.astype(jnp.bfloat16),
                      index.graph, index.entry_centers, index.entry_ids)
    params = cagra.SearchParams(itopk_size=ITOPK)
    seeds = cagra.make_seed_ids(params, index, jnp.asarray(qs), K)
    d, i = cagra.search(params, low, qs, K, seed_ids=seeds)
    # the reference scores the ids the program returned, in float32
    ids = np.asarray(i)
    exact = np.stack([
        beam_reference._distances(x, qs[r], ids[r], "sqeuclidean")
        for r in range(qs.shape[0])])
    gap = _gap(x, qs, d, ids, exact, "sqeuclidean")
    assert gap.max() > 10 * DIST_TOL
    # and the float32 rows pass the same comparison
    d32, i32 = cagra.search(params, index, qs, K, seed_ids=seeds)
    ids32 = np.asarray(i32)
    exact32 = np.stack([
        beam_reference._distances(x, qs[r], ids32[r], "sqeuclidean")
        for r in range(qs.shape[0])])
    assert _gap(x, qs, d32, ids32, exact32, "sqeuclidean").max() <= DIST_TOL


def _stats_after(svc, name, batches, timeout=30.0):
    """The service's stats once ``batches`` dispatches are recorded (the
    record stage runs after the futures resolve)."""
    t_end = time.monotonic() + timeout
    while True:
        st = svc.stats(name)
        if st["batches"] >= batches or time.monotonic() > t_end:
            return st
        time.sleep(0.01)


@pytest.mark.parametrize("metric", ["sqeuclidean", "inner_product"])
def test_service_walk_matches_the_beam_reference(built, metric):
    """Through ``SearchService``: the dispatch pads the request to its
    bucket and seeds the padded batch; the reference walks each real row
    from those seeds.  The service's counter sums the real rows' walks."""
    index, x, qs = built[metric]
    params = cagra.SearchParams(itopk_size=ITOPK)
    mi = serve.MutableIndex(index, search_params=params)
    bucket = 64
    svc = serve.SearchService(k=K, min_bucket=bucket, max_batch=bucket,
                              max_delay_ms=1.0)
    try:
        svc.add_index("graph", mi, warmup=True)
        b0 = svc.stats("graph")["batches"]
        d, i = svc.submit("graph", qs).result(timeout=120)
        st = _stats_after(svc, "graph", b0 + 1)
    finally:
        svc.stop()
    padded = np.zeros((bucket, D), np.float32)
    padded[: qs.shape[0]] = qs
    seeds = np.asarray(cagra.make_seed_ids(params, index, jnp.asarray(padded),
                                           K))[: qs.shape[0]]
    want_d, want_i, want_e = _reference(index, qs, seeds, metric, 1)
    _assert_agrees(x, qs, d, i, want_d, want_i, metric)
    work = st["search_work"]
    assert work["dispatches"] == 1 and work["rows"] == qs.shape[0]
    assert work["expansions"] == int(want_e.sum())
    assert work["iterations"] >= int(want_e.max())
    assert set(st["kernel_paths"]) == {"xla"}


def test_benchmark_algo_serves_what_cagra_search_returns():
    """``benchmark/algos/cagra.build`` at a tiny size gives a CAGRA
    ``MutableIndex`` whose served answers are ``cagra.search``'s on the
    same padded batch."""
    algo = _load("benchmark/algos/cagra.py", "algo_cagra")
    x, qs = _mixture(27, 1500, 40, False)
    cfg = {"metric": "sqeuclidean", "k": K, "index": {
        "kind": "cagra", "refine": False, "scan_elem_bytes": 4,
        "build": {"graph_degree": 16, "intermediate_graph_degree": 32,
                  "build_algo": "brute_force"},
        "search": {"itopk_size": 32, "search_width": 1}}}
    mi, shapes = algo.build(cfg, jnp.asarray(x))
    assert isinstance(mi, serve.MutableIndex) and mi.kind == "cagra"
    assert shapes["graph_degree"] == 16 and shapes["itopk"] == 32
    svc = serve.SearchService(k=K, min_bucket=64, max_batch=64,
                              max_delay_ms=1.0)
    try:
        svc.add_index("algo", mi, warmup=True)
        d, i = svc.submit("algo", qs).result(timeout=120)
    finally:
        svc.stop()
    padded = np.zeros((64, D), np.float32)
    padded[: qs.shape[0]] = qs
    want_d, want_i = cagra.search(mi.search_params, mi.index, padded, K)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(want_i)[:40])
    np.testing.assert_allclose(np.asarray(d), np.asarray(want_d)[:40],
                               rtol=1e-6)


def test_ivf_paths_keep_no_search_work():
    """The counter belongs to the graph walk: an IVF index's dispatches
    stamp nothing, and its stats carry no ``search_work``."""
    x, qs = _mixture(28, 2000, 20, False)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=8), x)
    mi = serve.MutableIndex(index,
                            search_params=ivf_flat.SearchParams(n_probes=2))
    kernels.consume_search_work()
    svc = serve.SearchService(k=K, min_bucket=32, max_batch=32,
                              max_delay_ms=1.0)
    try:
        svc.add_index("ivf", mi, warmup=True)
        b0 = svc.stats("ivf")["batches"]
        svc.submit("ivf", qs).result(timeout=120)
        st = _stats_after(svc, "ivf", b0 + 1)
    finally:
        svc.stop()
    assert st["batches"] == b0 + 1
    assert "search_work" not in st
    assert kernels.consume_search_work() is None


def test_search_work_stamp_skips_traced_calls():
    """A search traced inside an outer jit must not leave its tracer in
    the thread-local stamp."""
    kernels.consume_search_work()

    @jax.jit
    def f(w):
        kernels.stamp_search_work(w, 1)
        return w + 1

    f(jnp.zeros(3, jnp.int32))
    assert kernels.consume_search_work() is None
    kernels.stamp_search_work(jnp.ones(3, jnp.int32), 2)
    work, n = kernels.consume_search_work()
    assert n == 2 and int(np.asarray(work).sum()) == 3


def _dot_precisions(jaxpr, out):
    """Precision of every ``dot_general`` in ``jaxpr`` and its sub-jaxprs
    (a ``pallas_call`` carries its kernel's jaxpr as a parameter)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                if hasattr(sub, "eqns"):
                    _dot_precisions(sub, out)
                elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                    _dot_precisions(sub.jaxpr, out)
    return out


@pytest.mark.parametrize("metric", ["sqeuclidean", "inner_product"])
def test_fused_hop_scores_at_highest_precision(metric):
    """Interpret mode computes a DEFAULT-precision f32 dot exactly; the
    chip's Mosaic runs it as one bf16 pass (the squared norms of the rows
    read ~4e-4 of the pair's scale off there).  So the kernel's own dots
    are held to HIGHEST here, where the CPU cannot show the difference."""
    from raft_tpu.kernels.cagra_traverse import cagra_fused_hop

    n, tile, itopk = 64, 8, 32
    args = (jnp.zeros((n, D)), jnp.zeros((n, DEGREE), jnp.int32),
            jnp.zeros((tile, D)), jnp.zeros((tile, 1), jnp.int32),
            jnp.zeros((tile, itopk)), jnp.zeros((tile, itopk), jnp.int32),
            jnp.zeros((tile, itopk), bool))
    jaxpr = jax.make_jaxpr(
        lambda *a: cagra_fused_hop(*a, metric=metric))(*args).jaxpr
    precisions = _dot_precisions(jaxpr, [])
    assert len(precisions) == (1 if metric == "inner_product" else 2)
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    assert all(p == highest for p in precisions), precisions
