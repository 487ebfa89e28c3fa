"""Fused Pallas k-selection (kernels/select_k.py), validated in
interpret mode on CPU.

The routing contract is *exact match* — not recall — against both XLA
paths: ``matrix.select_k``'s lowest-position-wins tie break and
``select_k_stable``'s smallest-id-wins discipline.  The suites here
drive heavy-tie inputs (quantized values, duplicate ids, sentinel −1
ids, +inf merge padding) because the tie break is exactly where a
selection kernel silently diverges.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.kernels.select_k import select_k_pallas, select_k_supported
from raft_tpu.ops import matrix


# -- direct kernel parity (routing-independent) -----------------------------

@pytest.mark.parametrize("rows,n,k", [(5, 37, 7), (8, 128, 16), (3, 1000, 32), (1, 8, 8)])
@pytest.mark.parametrize("select_min", [True, False])
def test_positional_parity_vs_topk(rng, rows, n, k, select_min):
    # quantized values force ties; top_k resolves them lowest-index-first
    s = jnp.asarray(
        np.round(rng.standard_normal((rows, n)) * 3).astype(np.float32)
    )
    v0, i0 = matrix.select_k(s, k, select_min=select_min, algo="topk")
    v1, i1 = select_k_pallas(s, k, select_min=select_min, interpret=True)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_parity(rng, dtype):
    s = jnp.asarray(rng.standard_normal((6, 300)).astype(np.float32)).astype(dtype)
    v0, i0 = matrix.select_k(s, 12, algo="topk")
    v1, i1 = select_k_pallas(s, 12, interpret=True)
    assert v1.dtype == s.dtype
    np.testing.assert_array_equal(np.asarray(v0, np.float32), np.asarray(v1, np.float32))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_input_indices_and_inf_padding(rng):
    # serving-merge shape: inf-padded slots carrying −1 sentinel ids must
    # come out exactly like the XLA path (values inf, ids −1, sorted last)
    rows, n, k = 4, 96, 24
    s = np.round(rng.standard_normal((rows, n)) * 2).astype(np.float32)
    s[:, 70:] = np.inf
    ids = rng.integers(0, 10_000, size=(rows, n)).astype(np.int32)
    ids[:, 70:] = -1
    s, ids = jnp.asarray(s), jnp.asarray(ids)
    v0, i0 = matrix.select_k(s, k, algo="topk", input_indices=ids)
    v1, i1 = select_k_pallas(s, k, input_indices=ids, interpret=True)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_stable_parity_heavy_ties(rng):
    # many duplicate values AND duplicate/negative ids: the stable
    # discipline (smallest id wins, negatives lose every tie → −1) must
    # match select_k_stable bitwise
    rows, n, k = 7, 256, 32
    s = np.asarray(rng.integers(0, 4, size=(rows, n)), np.float32)
    ids = rng.integers(-1, 50, size=(rows, n)).astype(np.int32)
    s, ids = jnp.asarray(s), jnp.asarray(ids)
    v0, i0 = matrix.select_k_stable(s, k, input_indices=ids)
    v1, i1 = select_k_pallas(s, k, stable=True, input_indices=ids, interpret=True)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_stable_partition_invariance(rng):
    # the property select_k_stable exists for: merging the same candidate
    # multiset in any order/partition yields identical winners
    n, k = 180, 16
    s = np.asarray(rng.integers(0, 5, size=(1, n)), np.float32)
    ids = np.asarray(rng.permutation(n), np.int32)[None, :]
    perm = rng.permutation(n)
    v0, i0 = select_k_pallas(
        jnp.asarray(s), k, stable=True, input_indices=jnp.asarray(ids),
        interpret=True,
    )
    v1, i1 = select_k_pallas(
        jnp.asarray(s[:, perm]), k, stable=True,
        input_indices=jnp.asarray(ids[:, perm]), interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_supported_envelope():
    assert select_k_supported(512, 32, jnp.float32)
    assert select_k_supported(8192, 128, jnp.bfloat16)
    assert not select_k_supported(8193, 32, jnp.float32)   # too wide
    assert not select_k_supported(512, 129, jnp.float32)   # k too deep
    assert not select_k_supported(16, 32, jnp.float32)     # k > n
    assert not select_k_supported(512, 32, jnp.int32)      # int rows
    with pytest.raises(ValueError):
        select_k_pallas(jnp.zeros((2, 16), jnp.int32), 4, interpret=True)


# -- routing through ops.matrix --------------------------------------------

class TestRouting:
    def test_auto_routes_to_kernel(self, rng, monkeypatch):
        # non-vacuity: prove algo="auto" actually reaches the kernel by
        # making it explode
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        from raft_tpu.kernels import select_k as sk_mod

        def boom(*a, **kw):
            raise RuntimeError("kernel reached")

        monkeypatch.setattr(sk_mod, "select_k_pallas", boom)
        s = jnp.asarray(rng.standard_normal((3, 200)).astype(np.float32))
        with pytest.raises(RuntimeError, match="kernel reached"):
            matrix.select_k(s, 10)
        # the per-kernel revert knob must bypass it
        monkeypatch.setenv("RAFT_TPU_PALLAS_SELECT_K", "0")
        v, i = matrix.select_k(s, 10)
        assert v.shape == (3, 10)
        # an explicit algo= request is honored verbatim (no kernel)
        monkeypatch.setenv("RAFT_TPU_PALLAS_SELECT_K", "1")
        v, i = matrix.select_k(s, 10, algo="topk")
        assert v.shape == (3, 10)

    def test_routed_matches_xla_with_row_k(self, rng, monkeypatch):
        # ragged demotion: per-row k rides mask_row_k after the kernel
        s = jnp.asarray(rng.standard_normal((6, 150)).astype(np.float32))
        row_k = jnp.asarray([1, 3, 8, 8, 5, 2], jnp.int32)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "0")
        v0, i0 = matrix.select_k(s, 8, row_k=row_k)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v1, i1 = matrix.select_k(s, 8, row_k=row_k)
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))

    def test_routed_stable_matches_xla(self, rng, monkeypatch):
        s = np.asarray(rng.integers(0, 3, size=(4, 220)), np.float32)
        ids = rng.integers(-1, 64, size=(4, 220)).astype(np.int32)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "0")
        v0, i0 = matrix.select_k_stable(jnp.asarray(s), 16, input_indices=jnp.asarray(ids))
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        v1, i1 = matrix.select_k_stable(jnp.asarray(s), 16, input_indices=jnp.asarray(ids))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))

    def test_1d_squeeze_and_chunked_precedence(self, rng, monkeypatch):
        monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
        s = jnp.asarray(rng.standard_normal(500).astype(np.float32))
        v, i = matrix.select_k(s, 5)
        assert v.shape == (5,) and i.shape == (5,)
        # wide rows with small k stay on the chunked tournament — the
        # kernel's MAX_N envelope and the chunked gate must compose
        wide = jnp.asarray(rng.standard_normal((2, 10_000)).astype(np.float32))
        v0, i0 = matrix.select_k(wide, 4)
        monkeypatch.setenv("RAFT_TPU_PALLAS", "0")
        v1, i1 = matrix.select_k(wide, 4)
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
