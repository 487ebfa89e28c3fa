"""Pallas TPU kernels, validated in interpret mode on CPU
(SURVEY §5: interpret=True doubles as the OOB sanitizer)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.kernels.fused_argmin import fused_l2_argmin
from raft_tpu.kernels.fused_knn import fused_l2_topk


@pytest.mark.parametrize("n,d,n_q,k", [(1000, 32, 64, 10), (700, 100, 33, 17)])
def test_fused_l2_topk_matches_exact(rng, n, d, n_q, k):
    x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((n_q, d)).astype(np.float32))
    xx = jnp.sum(x * x, axis=1)
    vals, idx = fused_l2_topk(q, x, xx, k, interpret=True)
    # exact reference: full distance matrix
    d2 = (
        xx[None, :]
        - 2.0 * jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST)
    )
    want_idx = np.argsort(np.asarray(d2), axis=1, kind="stable")[:, :k]
    want_vals = np.take_along_axis(np.asarray(d2), want_idx, axis=1)
    np.testing.assert_allclose(np.asarray(vals), want_vals, rtol=1e-4, atol=1e-4)
    # indices may differ on ties; value sets must match
    assert (np.abs(np.asarray(vals) - want_vals) < 1e-3).all()


def test_fused_l2_topk_ip_mode(rng):
    n, d, n_q, k = 500, 64, 20, 8
    x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((n_q, d)).astype(np.float32))
    vals, idx = fused_l2_topk(q, x, jnp.zeros(n), k, mode="ip", interpret=True)
    ip = np.asarray(jnp.matmul(q, x.T, precision=jax.lax.Precision.HIGHEST))
    want_idx = np.argsort(-ip, axis=1, kind="stable")[:, :k]
    got_scores = -np.asarray(vals)  # kernel returns negated IP ascending
    want_scores = np.take_along_axis(ip, want_idx, axis=1)
    np.testing.assert_allclose(got_scores, want_scores, rtol=1e-4, atol=1e-4)


def test_fused_l2_argmin_matches_exact(rng):
    n, d, c = 2000, 48, 100
    x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    centers = jnp.asarray(rng.standard_normal((c, d)).astype(np.float32))
    cc = jnp.sum(centers * centers, axis=1)
    vals, idx = fused_l2_argmin(x, centers, cc, interpret=True)
    d2 = np.asarray(
        cc[None, :]
        - 2.0 * jnp.matmul(x, centers.T, precision=jax.lax.Precision.HIGHEST)
    )
    want = np.argmin(d2, axis=1)
    # ties can pick either index; compare scores
    got_scores = np.asarray(vals)
    want_scores = d2[np.arange(n), want]
    np.testing.assert_allclose(got_scores, want_scores, rtol=1e-4, atol=1e-4)
    assert (np.asarray(idx) == want).mean() > 0.999  # ties are measure-zero


class TestToolkit:
    """Kernel toolkit building blocks (ref: cpp/include/raft/util/ +
    linalg/contractions.cuh tiling policies)."""

    def test_address_math(self):
        from raft_tpu.kernels import toolkit as tk

        assert tk.cdiv(10, 3) == 4
        assert tk.round_up(100, 128) == 128
        assert tk.next_pow2(100) == 128 and tk.next_pow2(1) == 1
        x = jnp.ones((5, 7))
        p = tk.pad_dim(x, 1, 8, fill=-1.0)
        assert p.shape == (5, 8) and float(p[0, 7]) == -1.0
        assert tk.pad_dim(x, 0, 5) is x

    def test_tile_policy_fits_budget(self):
        from raft_tpu.kernels import toolkit as tk

        pol = tk.choose_tile_policy(10_000, 1_000_000, 96, extra_cols=128)
        assert pol.vmem_bytes <= 8 * 1024 * 1024
        assert pol.tile_m % tk.SUBLANE == 0 and pol.tile_n % tk.LANE == 0
        assert pol.grid[0] * pol.tile_m >= 10_000
        assert pol.grid[1] * pol.tile_n >= 1_000_000
        small = tk.choose_tile_policy(16, 100, 8)
        assert small.tile_m <= 512 and small.grid == (1, 1)

    def test_fold_topk_matches_sort(self, rng):
        from raft_tpu.kernels import toolkit as tk

        rows, k_pad, c, k = 6, 32, 100, 9
        run_v = jnp.full((rows, k_pad), float("inf"))
        run_i = jnp.zeros((rows, k_pad), jnp.int32)
        a = rng.standard_normal((rows, c)).astype(np.float32)
        ia = jnp.asarray(rng.integers(0, 10_000, (rows, c)).astype(np.int32))
        v1, i1 = tk.fold_topk(run_v, run_i, jnp.asarray(a), ia, k)
        # second fold with more candidates must equal top-k of the union
        b = rng.standard_normal((rows, c)).astype(np.float32)
        ib = jnp.asarray(rng.integers(10_000, 20_000, (rows, c)).astype(np.int32))
        v2, i2 = tk.fold_topk(v1, i1, jnp.asarray(b), ib, k)
        union = np.concatenate([a, b], axis=1)
        union_i = np.concatenate([np.asarray(ia), np.asarray(ib)], axis=1)
        order = np.argsort(union, axis=1)[:, :k]
        np.testing.assert_allclose(
            np.asarray(v2)[:, :k], np.take_along_axis(union, order, 1), rtol=1e-6
        )
        np.testing.assert_array_equal(
            np.asarray(i2)[:, :k], np.take_along_axis(union_i, order, 1)
        )
        # slots past k hold the worst sentinel
        assert np.isinf(np.asarray(v2)[:, k:]).all()


def test_tile_policy_alignment_under_pressure():
    """Shrinking under a tight VMEM budget must keep native alignment
    (regression: halving a non-power-of-two start left off-quantum tiles)."""
    from raft_tpu.kernels import toolkit as tk

    p1 = tk.choose_tile_policy(16, 640, 8192)
    assert p1.tile_n % tk.LANE == 0 and p1.tile_m % tk.SUBLANE == 0
    p2 = tk.choose_tile_policy(40, 100_000, 4096, vmem_budget=2 * 1024 * 1024)
    assert p2.tile_m % tk.SUBLANE == 0 and p2.tile_m >= tk.SUBLANE
    assert p2.tile_n % tk.LANE == 0 and p2.tile_n >= tk.LANE


class TestIvfScanKernel:
    """Fused Pallas probe-major IVF scan (kernels/ivf_scan.py) must agree
    with the XLA probe-major schedule exactly (interpret mode; the compile
    leg lives in tests/test_tpu_compile.py)."""

    def _index(self, n=8000, d=32):
        from raft_tpu.neighbors import ivf_pq
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(0)
        x, _, _ = make_blobs(key, n, d, n_clusters=32, cluster_std=2.0)
        x = np.asarray(x)
        return (
            ivf_pq.build(
                ivf_pq.IndexParams(n_lists=32, pq_dim=16, kmeans_n_iters=4), x
            ),
            x,
        )

    def test_matches_xla_probe_major(self, monkeypatch):
        from raft_tpu.neighbors import ivf_pq

        index, x = self._index()
        q = jnp.asarray(x[:300] + 0.01)
        sp = ivf_pq.SearchParams(n_probes=8, strategy="probe_major")
        v_x, i_x = ivf_pq.search(sp, index, q, 10)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_pq.search(sp, index, q, 10)
        assert (np.asarray(i_x) == np.asarray(i_p)).mean() >= 0.99
        np.testing.assert_allclose(
            np.asarray(v_x), np.asarray(v_p), rtol=2e-3, atol=1e-3
        )

    def test_filtered_matches_xla(self, monkeypatch):
        """Round 4: bitset filters ride the kernel's packed per-list word
        table — the filtered Pallas scan must agree with the filtered XLA
        schedule and never surface a filtered-out id."""
        from raft_tpu.core.bitset import Bitset
        from raft_tpu.neighbors import ivf_pq

        index, x = self._index(n=4000)
        q = jnp.asarray(x[:300])
        sp = ivf_pq.SearchParams(n_probes=8, strategy="probe_major")
        mask = np.zeros(x.shape[0], bool)
        mask[::2] = True
        bs = Bitset.from_mask(jnp.asarray(mask))
        v_x, i_x = ivf_pq.search(sp, index, q, 5, sample_filter=bs)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_pq.search(sp, index, q, 5, sample_filter=bs)
        i_p_np = np.asarray(i_p)
        assert (i_p_np[i_p_np >= 0] % 2 == 0).all()
        assert (np.asarray(i_x) == i_p_np).mean() >= 0.99
        np.testing.assert_allclose(
            np.asarray(v_x), np.asarray(v_p), rtol=2e-3, atol=1e-3
        )

    def test_inner_product_matches_xla(self, monkeypatch):
        """Round 4: the kernel's −ip leg must agree with the XLA
        inner-product probe-major schedule."""
        from raft_tpu.neighbors import ivf_pq
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(1)
        xi, _, _ = make_blobs(key, 6000, 32, n_clusters=24, cluster_std=2.0)
        xi = np.asarray(xi)
        idx_ip = ivf_pq.build(
            ivf_pq.IndexParams(
                n_lists=24, pq_dim=16, kmeans_n_iters=4,
                metric="inner_product",
            ),
            xi,
        )
        q = jnp.asarray(xi[:300] + 0.01)
        sp = ivf_pq.SearchParams(n_probes=8, strategy="probe_major")
        v_x, i_x = ivf_pq.search(sp, idx_ip, q, 10)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_pq.search(sp, idx_ip, q, 10)
        assert (np.asarray(i_x) == np.asarray(i_p)).mean() >= 0.99
        np.testing.assert_allclose(
            np.asarray(v_x), np.asarray(v_p), rtol=2e-3, atol=1e-3
        )

    def test_filtered_int8_matches_xla(self, monkeypatch):
        """Composition: int8 quantized cache × bitset filter through the
        kernel — the DEEP-100M memory-lean mode with a sample filter."""
        from raft_tpu.core.bitset import Bitset
        from raft_tpu.neighbors import ivf_pq
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(7)
        x, _, _ = make_blobs(key, 6000, 32, n_clusters=24, cluster_std=2.0)
        x = np.asarray(x)
        index = ivf_pq.build(
            ivf_pq.IndexParams(
                n_lists=24, pq_dim=16, kmeans_n_iters=4,
                decoded_dtype="int8",
            ),
            x,
        )
        q = jnp.asarray(x[:300] + 0.01)
        sp = ivf_pq.SearchParams(n_probes=8, strategy="probe_major")
        mask = np.zeros(x.shape[0], bool)
        mask[1::2] = True
        bs = Bitset.from_mask(jnp.asarray(mask))
        v_x, i_x = ivf_pq.search(sp, index, q, 5, sample_filter=bs)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_pq.search(sp, index, q, 5, sample_filter=bs)
        i_p_np = np.asarray(i_p)
        assert (i_p_np[i_p_np >= 0] % 2 == 1).all()
        assert (np.asarray(i_x) == i_p_np).mean() >= 0.99

    def test_ivf_flat_pallas_matches_xla(self, monkeypatch):
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(2)
        x, _, _ = make_blobs(key, 6000, 32, n_clusters=24, cluster_std=2.0)
        x = np.asarray(x)
        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=24, kmeans_n_iters=4), x
        )
        q = jnp.asarray(x[:300] + 0.01)
        sp = ivf_flat.SearchParams(n_probes=6, strategy="probe_major")
        v_x, i_x = ivf_flat.search(sp, index, q, 10)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_flat.search(sp, index, q, 10)
        assert (np.asarray(i_x) == np.asarray(i_p)).mean() >= 0.99
        np.testing.assert_allclose(
            np.asarray(v_x), np.asarray(v_p), rtol=2e-3, atol=1e-3
        )

    def test_ivf_flat_filtered_and_ip_match_xla(self, monkeypatch):
        """Round 4: ivf_flat's filtered and inner-product probe-major
        searches ride the widened kernel and must agree with XLA."""
        from raft_tpu.core.bitset import Bitset
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(3)
        x, _, _ = make_blobs(key, 4000, 16, n_clusters=16, cluster_std=2.0)
        x = np.asarray(x)
        index = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=3), x
        )
        q = jnp.asarray(x[:300])
        sp = ivf_flat.SearchParams(n_probes=8, strategy="probe_major")
        mask = np.zeros(x.shape[0], bool)
        mask[::2] = True
        bs = Bitset.from_mask(jnp.asarray(mask))
        v_x, i_x = ivf_flat.search(sp, index, q, 5, sample_filter=bs)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_flat.search(sp, index, q, 5, sample_filter=bs)
        i_p_np = np.asarray(i_p)
        assert (i_p_np[i_p_np >= 0] % 2 == 0).all()
        assert (np.asarray(i_x) == i_p_np).mean() >= 0.99
        # inner product through the kernel's −ip leg
        idx_ip = ivf_flat.build(
            ivf_flat.IndexParams(
                n_lists=16, kmeans_n_iters=3, metric="inner_product"
            ), x,
        )
        monkeypatch.delenv("RAFT_TPU_PALLAS")
        v_xi, i_xi = ivf_flat.search(sp, idx_ip, q, 5)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_pi, i_pi = ivf_flat.search(sp, idx_ip, q, 5)
        assert (np.asarray(i_xi) == np.asarray(i_pi)).mean() >= 0.99
        np.testing.assert_allclose(
            np.asarray(v_xi), np.asarray(v_pi), rtol=2e-3, atol=1e-3
        )

    def test_ivf_flat_cosine_matches_xla(self, monkeypatch):
        """Round 4 widening: cosine rides the kernel's normalized leg and
        must agree with the XLA schedule (same rsqrt floors)."""
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(3)
        x, _, _ = make_blobs(key, 4000, 16, n_clusters=16, cluster_std=2.0)
        x = np.asarray(x)
        q = jnp.asarray(x[:300])
        sp = ivf_flat.SearchParams(n_probes=8, strategy="probe_major")
        idx_cos = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=3, metric="cosine"), x
        )
        v_x, i_x = ivf_flat.search(sp, idx_cos, q, 5)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        # prove the kernel path actually dispatches (a gate regression
        # would otherwise make this equivalence vacuous)
        monkeypatch.setattr(
            ivf_flat, "_search_probe_major_jit",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("XLA path taken despite RAFT_TPU_PALLAS=1")
            ),
        )
        v_p, i_p = ivf_flat.search(sp, idx_cos, q, 5)
        assert (np.asarray(i_x) == np.asarray(i_p)).mean() >= 0.99
        np.testing.assert_allclose(
            np.asarray(v_x), np.asarray(v_p), rtol=2e-3, atol=1e-3
        )

    def test_ivf_flat_gate_excludes_raw_int8(self, monkeypatch):
        """Raw int8 datasets (no dequant scale) must still route to the
        XLA schedule."""
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(3)
        x, _, _ = make_blobs(key, 4000, 16, n_clusters=16, cluster_std=2.0)
        x = np.asarray(x)
        q = jnp.asarray(x[:300])
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")

        def boom(*a, **k):
            raise AssertionError("Pallas path taken for an excluded case")

        monkeypatch.setattr(ivf_flat, "_search_probe_major_pallas", boom)
        sp = ivf_flat.SearchParams(n_probes=8, strategy="probe_major")
        x8 = (x * 10).astype(np.int8)
        idx_i8 = ivf_flat.build(
            ivf_flat.IndexParams(n_lists=16, kmeans_n_iters=3), x8
        )
        ivf_flat.search(sp, idx_i8, q, 5)

    def test_int8_cache_matches_xla(self, monkeypatch):
        """The kernel's quantized-query int8 leg (the memory-lean
        DEEP-100M mode, fused) must agree with the XLA int8 probe-major
        schedule."""
        from raft_tpu.neighbors import ivf_pq
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(4)
        x, _, _ = make_blobs(key, 6000, 32, n_clusters=24, cluster_std=2.0)
        x = np.asarray(x)
        index = ivf_pq.build(
            ivf_pq.IndexParams(
                n_lists=24, pq_dim=16, kmeans_n_iters=4, decoded_dtype="int8"
            ),
            x,
        )
        q = jnp.asarray(x[:300] + 0.01)
        sp = ivf_pq.SearchParams(n_probes=6, strategy="probe_major")
        v_x, i_x = ivf_pq.search(sp, index, q, 10)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_pq.search(sp, index, q, 10)
        assert (np.asarray(i_x) == np.asarray(i_p)).mean() >= 0.99
        np.testing.assert_allclose(
            np.asarray(v_x), np.asarray(v_p), rtol=2e-3, atol=1e-3
        )


class TestIvfScanQueryMajor:
    """Fused query-major scan (ivf_scan_query_major) must agree with the
    XLA query-major schedule (interpret mode; Mosaic leg in
    tests/test_tpu_compile.py)."""

    def _index(self, decoded_dtype="bfloat16", n=8000, d=32):
        from raft_tpu.neighbors import ivf_pq
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(6)
        x, _, _ = make_blobs(key, n, d, n_clusters=32, cluster_std=2.0)
        x = np.asarray(x)
        return x, ivf_pq.build(
            ivf_pq.IndexParams(
                n_lists=32, pq_dim=d // 2, kmeans_n_iters=4,
                decoded_dtype=decoded_dtype,
            ), x,
        )

    def test_matches_xla_query_major(self, monkeypatch):
        from raft_tpu.neighbors import ivf_pq

        x, index = self._index()
        q = jnp.asarray(x[:301] + 0.01)   # non-multiple of 8: pad leg
        sp = ivf_pq.SearchParams(n_probes=6, strategy="query_major")
        v_x, i_x = ivf_pq.search(sp, index, q, 10)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        # prove the fused path dispatches
        monkeypatch.setattr(
            ivf_pq, "_search_jit",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("XLA query-major taken despite gate")
            ),
        )
        v_p, i_p = ivf_pq.search(sp, index, q, 10)
        assert (np.asarray(i_x) == np.asarray(i_p)).mean() >= 0.99
        np.testing.assert_allclose(
            np.asarray(v_x), np.asarray(v_p), rtol=2e-3, atol=1e-3
        )

    def test_filtered_and_int8_match_xla(self, monkeypatch):
        from raft_tpu.core.bitset import Bitset
        from raft_tpu.neighbors import ivf_pq

        x, index = self._index()
        q = jnp.asarray(x[:96] + 0.01)
        sp = ivf_pq.SearchParams(n_probes=8, strategy="query_major")
        mask = np.zeros(x.shape[0], bool)
        mask[::2] = True
        bs = Bitset.from_mask(jnp.asarray(mask))
        v_x, i_x = ivf_pq.search(sp, index, q, 5, sample_filter=bs)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_pq.search(sp, index, q, 5, sample_filter=bs)
        i_p_np = np.asarray(i_p)
        assert (i_p_np[i_p_np >= 0] % 2 == 0).all()
        assert (np.asarray(i_x) == i_p_np).mean() >= 0.99
        # int8 scan cache through the quantized-query leg
        monkeypatch.delenv("RAFT_TPU_PALLAS")
        x8, idx8 = self._index(decoded_dtype="int8")
        q8 = jnp.asarray(x8[:96] + 0.01)
        v_x8, i_x8 = ivf_pq.search(sp, idx8, q8, 10)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p8, i_p8 = ivf_pq.search(sp, idx8, q8, 10)
        assert (np.asarray(i_x8) == np.asarray(i_p8)).mean() >= 0.99

    def test_vmem_gate_falls_back(self, monkeypatch):
        """Past the scratch budget the dispatch must stay on XLA (budget
        shrunk below any real scratch so the fallback is actually
        exercised)."""
        from raft_tpu.neighbors import ivf_pq

        x, index = self._index()
        q = jnp.asarray(x[:32])
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        monkeypatch.setattr(
            ivf_pq, "_search_query_major_pallas",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("pallas query-major taken past VMEM gate")
            ),
        )
        from raft_tpu.kernels import ivf_scan

        monkeypatch.setattr(ivf_scan, "QM_VMEM_BUDGET", 0)
        sp = ivf_pq.SearchParams(n_probes=6, strategy="query_major")
        v, i = ivf_pq.search(sp, index, q, 5)
        assert np.asarray(i).shape == (32, 5)

    def test_ivf_flat_query_major_matches_xla(self, monkeypatch):
        """ivf_flat rides the same payload-agnostic kernel (norms as y²,
        unrotated queries) — L2, cosine, and filtered IP legs."""
        from raft_tpu.core.bitset import Bitset
        from raft_tpu.neighbors import ivf_flat
        from raft_tpu.random import make_blobs

        key = jax.random.PRNGKey(7)
        x, _, _ = make_blobs(key, 6000, 32, n_clusters=24, cluster_std=2.0)
        x = np.asarray(x)
        q = jnp.asarray(x[:203] + 0.01)
        sp = ivf_flat.SearchParams(n_probes=6, strategy="query_major")
        for metric in ("sqeuclidean", "cosine"):
            idx = ivf_flat.build(
                ivf_flat.IndexParams(
                    n_lists=24, kmeans_n_iters=4, metric=metric
                ), x,
            )
            monkeypatch.delenv("RAFT_TPU_PALLAS", raising=False)
            v_x, i_x = ivf_flat.search(sp, idx, q, 10)
            monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
            v_p, i_p = ivf_flat.search(sp, idx, q, 10)
            assert (np.asarray(i_x) == np.asarray(i_p)).mean() >= 0.99, metric
            np.testing.assert_allclose(
                np.asarray(v_x), np.asarray(v_p), rtol=2e-3, atol=1e-3
            )
        # filtered inner product
        idx_ip = ivf_flat.build(
            ivf_flat.IndexParams(
                n_lists=24, kmeans_n_iters=4, metric="inner_product"
            ), x,
        )
        mask = np.zeros(x.shape[0], bool)
        mask[::2] = True
        bs = Bitset.from_mask(jnp.asarray(mask))
        monkeypatch.delenv("RAFT_TPU_PALLAS", raising=False)
        v_x, i_x = ivf_flat.search(sp, idx_ip, q, 5, sample_filter=bs)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_flat.search(sp, idx_ip, q, 5, sample_filter=bs)
        i_p_np = np.asarray(i_p)
        assert (i_p_np[i_p_np >= 0] % 2 == 0).all()
        assert (np.asarray(i_x) == i_p_np).mean() >= 0.99


class TestIvfPqDescriptorLeg:
    """PR 13: ivf_pq's fused query-major leg gains the packed per-list
    filter-word descriptor (the leg ivf_flat already rides) — ragged
    per-row-filtered traffic must stamp ``kernel_path=pallas``, not
    ``xla_filter_fallback``, and agree with the XLA fallback."""

    def _setup(self, seed=3, n=3000, d=32, q=24, n_filters=3):
        from raft_tpu.core.bitset import RowFilter
        from raft_tpu.neighbors import ivf_pq

        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, d)).astype(np.float32)
        queries = rng.normal(size=(q, d)).astype(np.float32)
        index = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=16, kmeans_n_iters=3), x
        )
        n_words = (n + 31) // 32
        table = np.zeros((n_filters, n_words), np.uint32)
        for f in range(n_filters):
            bits = rng.random(n) < 0.6
            packed = np.packbits(bits, bitorder="little")
            packed = np.pad(packed, (0, 4 * n_words - packed.size))
            table[f] = packed.view(np.uint32)
        fid = rng.integers(0, n_filters, size=q).astype(np.int32)
        filt = RowFilter.from_table(table, fid, n)
        return index, queries, table, fid, filt

    def test_descriptor_traffic_stays_pallas(self, monkeypatch):
        from raft_tpu import kernels
        from raft_tpu.neighbors import ivf_pq

        index, queries, table, fid, filt = self._setup()
        sp = ivf_pq.SearchParams(n_probes=16, strategy="query_major")
        monkeypatch.setenv("RAFT_TPU_PALLAS", "0")
        v_x, i_x = ivf_pq.search(sp, index, queries, 10, sample_filter=filt)
        assert kernels.consume_kernel_path() == "xla_filter_fallback"
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v_p, i_p = ivf_pq.search(sp, index, queries, 10, sample_filter=filt)
        assert kernels.consume_kernel_path() == "pallas"
        i_p_np = np.asarray(i_p)
        np.testing.assert_array_equal(np.asarray(i_x), i_p_np)
        np.testing.assert_allclose(
            np.asarray(v_x), np.asarray(v_p), rtol=2e-3, atol=1e-3
        )
        # every surfaced id passes its own row's filter
        for r in range(len(i_p_np)):
            for c in i_p_np[r]:
                if c >= 0:
                    assert (table[fid[r], c // 32] >> (c % 32)) & 1, (r, c)

    def test_plain_word_plane_still_falls_back(self, monkeypatch):
        # an ad-hoc per-row filter (no registered table) has no
        # descriptor: it must keep the fallback stamp, fused gate on
        from raft_tpu import kernels
        from raft_tpu.core.bitset import RowFilter
        from raft_tpu.neighbors import ivf_pq

        index, queries, table, fid, _ = self._setup()
        plain = RowFilter(jnp.asarray(table)[jnp.asarray(fid)], index.size)
        sp = ivf_pq.SearchParams(n_probes=16, strategy="query_major")
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        ivf_pq.search(sp, index, queries, 10, sample_filter=plain)
        assert kernels.consume_kernel_path() == "xla_filter_fallback"


@pytest.mark.parametrize("leg", ["brute_force", "ivf_pq"])
def test_prims_xla_arm_stays_xla_on_tpu(monkeypatch, leg):
    """The prims A/B's XLA arm pins RAFT_TPU_PALLAS=0: on a TPU an unset
    gate means auto (Pallas), which would time Pallas under the XLA label."""
    from raft_tpu import kernels
    from raft_tpu.bench.prims import pallas_arm
    from raft_tpu.neighbors import brute_force, ivf_pq

    rng = np.random.default_rng(0)
    x = rng.normal(size=(600, 16)).astype(np.float32)
    q = rng.normal(size=(8, 16)).astype(np.float32)
    if leg == "brute_force":
        def search(qq):
            return brute_force.knn(x, qq, 5)
    else:
        index = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=8, pq_dim=8, kmeans_n_iters=2), x
        )

        def search(qq):
            return ivf_pq.search(ivf_pq.SearchParams(n_probes=4), index, qq, 5)

    monkeypatch.delenv("RAFT_TPU_PALLAS", raising=False)
    monkeypatch.setattr(kernels, "_platform", lambda: "tpu")
    assert kernels.use_pallas()  # unset means auto: Pallas on a TPU
    pallas_arm(search, False)(q)
    assert kernels.consume_kernel_path() == "xla"
    assert "RAFT_TPU_PALLAS" not in os.environ  # restored
