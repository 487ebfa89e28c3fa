"""Fused Pallas CAGRA hop (kernels/cagra_traverse.py), validated in
interpret mode on CPU.

The fused hop is bit-equivalent to the XLA while-loop body up to value
ties at the itopk buffer's eviction boundary, so the acceptance gate is
*recall equivalence* on seeded graphs — the same gate the XLA legs hold
each other to (in practice the suites observe identical ids, asserted
as distance-multiset equality to stay tie-robust).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import kernels
from raft_tpu.neighbors import brute_force, cagra
from raft_tpu.serve.metrics import compile_count


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    x = rng.normal(size=(1500, 48)).astype(np.float32)
    q = x[rng.choice(1500, 24, replace=False)]
    q = q + rng.normal(0, 0.5, q.shape).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def built(corpus):
    x, _ = corpus
    return cagra.build(
        cagra.IndexParams(
            intermediate_graph_degree=48, graph_degree=16,
            build_algo="brute_force",
        ),
        x,
    )


def _recall(idx, gt):
    hits = sum(
        len(set(a.tolist()) & set(b.tolist()))
        for a, b in zip(np.asarray(idx), np.asarray(gt))
    )
    return hits / gt.size


@pytest.mark.parametrize("itopk", [32, 64])
def test_fused_matches_xla_hop(corpus, built, itopk, monkeypatch):
    x, q = corpus
    k = 10
    _, gt = brute_force.knn(x, q, k)
    monkeypatch.setenv("RAFT_TPU_PALLAS", "0")
    d0, i0 = cagra.search(cagra.SearchParams(itopk_size=itopk), built, q, k)
    assert kernels.consume_kernel_path() == "xla"
    monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
    d1, i1 = cagra.search(cagra.SearchParams(itopk_size=itopk), built, q, k)
    assert kernels.consume_kernel_path() == "pallas"
    r0, r1 = _recall(i0, gt), _recall(i1, gt)
    assert abs(r0 - r1) <= 0.02, (r0, r1)
    # distances must agree row-wise (ids may swap only across exact ties)
    np.testing.assert_allclose(
        np.asarray(d0), np.asarray(d1), rtol=1e-5, atol=1e-5
    )


def test_fused_inner_product(corpus, monkeypatch):
    x, q = corpus
    built_ip = cagra.build(
        cagra.IndexParams(
            intermediate_graph_degree=48, graph_degree=16,
            build_algo="brute_force", metric="inner_product",
        ),
        x,
    )
    _, gt = brute_force.knn(x, q, 10, metric="inner_product")
    monkeypatch.setenv("RAFT_TPU_PALLAS", "0")
    d0, i0 = cagra.search(cagra.SearchParams(itopk_size=64), built_ip, q, 10)
    monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
    d1, i1 = cagra.search(cagra.SearchParams(itopk_size=64), built_ip, q, 10)
    assert abs(_recall(i0, gt) - _recall(i1, gt)) <= 0.02
    np.testing.assert_allclose(
        np.asarray(d0), np.asarray(d1), rtol=1e-5, atol=1e-5
    )


def test_fused_bf16_dataset(corpus, built, monkeypatch):
    # bf16 rows DMA at half the bytes and upcast in VMEM
    x, q = corpus
    bf = cagra.Index(
        built.metric, jnp.asarray(x, jnp.bfloat16), built.graph,
        entry_centers=built.entry_centers, entry_ids=built.entry_ids,
    )
    _, gt = brute_force.knn(x, q, 10)
    monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
    _, i1 = cagra.search(cagra.SearchParams(itopk_size=64), bf, q, 10)
    assert kernels.consume_kernel_path() == "pallas"
    monkeypatch.setenv("RAFT_TPU_PALLAS", "0")
    _, i0 = cagra.search(cagra.SearchParams(itopk_size=64), bf, q, 10)
    assert abs(_recall(i0, gt) - _recall(i1, gt)) <= 0.02


def test_filtered_search_keeps_xla_leg(corpus, built, monkeypatch):
    # the result-buffer side-merge has no kernel leg: filtered traffic
    # must route (and stamp) xla even with the master gate on
    from raft_tpu.core.bitset import Bitset

    x, q = corpus
    monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
    bs = Bitset.from_mask(np.arange(len(x)) % 2 == 0)
    _, idx = cagra.search(
        cagra.SearchParams(itopk_size=64), built, q, 10, sample_filter=bs
    )
    assert kernels.consume_kernel_path() == "xla"
    got = np.asarray(idx)
    assert ((got % 2 == 0) | (got < 0)).all()


def test_revert_knob_routes_xla(corpus, built, monkeypatch):
    x, q = corpus
    monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
    monkeypatch.setenv("RAFT_TPU_PALLAS_CAGRA", "0")
    d0, i0 = cagra.search(cagra.SearchParams(itopk_size=32), built, q, 10)
    assert kernels.consume_kernel_path() == "xla"
    monkeypatch.setenv("RAFT_TPU_PALLAS_CAGRA", "1")
    d1, i1 = cagra.search(cagra.SearchParams(itopk_size=32), built, q, 10)
    assert kernels.consume_kernel_path() == "pallas"
    np.testing.assert_allclose(
        np.asarray(d0), np.asarray(d1), rtol=1e-5, atol=1e-5
    )


def test_routing_reaches_kernel(corpus, built, monkeypatch):
    # non-vacuity: the pallas stamp must mean the kernel actually traced
    import raft_tpu.kernels.cagra_traverse as ct

    x, q = corpus

    def boom(*a, **kw):
        raise RuntimeError("kernel reached")

    monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
    monkeypatch.setattr(ct, "cagra_fused_hop", boom)
    with pytest.raises(RuntimeError, match="kernel reached"):
        # fresh (itopk, k) combination so the jit cache cannot satisfy
        # the call without tracing
        cagra.search(cagra.SearchParams(itopk_size=48), built, q, 7)


def test_zero_post_warmup_recompiles_with_kernels_enabled(
    corpus, built, monkeypatch
):
    # shuffled traffic at a fixed shape must reuse one executable even
    # with the fused hop (and the routed select_k) enabled
    x, q = corpus
    rng = np.random.default_rng(5)
    monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
    sp = cagra.SearchParams(itopk_size=64)
    cagra.search(sp, built, q, 10)  # warmup
    c0 = compile_count()
    for _ in range(4):
        qq = q[rng.permutation(len(q))] + rng.normal(
            0, 0.1, q.shape
        ).astype(np.float32)
        cagra.search(sp, built, qq, 10)
        assert kernels.consume_kernel_path() == "pallas"
    assert compile_count() - c0 == 0, (
        "shuffled same-shape traffic recompiled with the fused hop on"
    )
