"""Core: resources, bitset, serialization (mirrors cpp/test/core/)."""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.core import Bitset, Resources, serialize


class TestResources:
    def test_lazy_factory(self):
        res = Resources()
        calls = []
        res.add_resource_factory("thing", lambda r: calls.append(1) or "made")
        assert res.get_resource("thing") == "made"
        assert res.get_resource("thing") == "made"
        assert len(calls) == 1  # factory ran once

    def test_missing_resource_raises(self):
        with pytest.raises(KeyError):
            Resources().get_resource("nope")

    def test_prng_stream_deterministic(self):
        a = Resources(seed=7)
        b = Resources(seed=7)
        ka = [np.asarray(a.prng_key()) for _ in range(3)]
        kb = [np.asarray(b.prng_key()) for _ in range(3)]
        np.testing.assert_array_equal(np.stack(ka), np.stack(kb))
        assert not np.array_equal(ka[0], ka[1])

    def test_workspace_rows(self):
        res = Resources(workspace_limit_bytes=1024)
        assert res.workspace_rows(128) == 8


class TestBitset:
    def test_create_set_test(self):
        bs = Bitset.create(100, default=False)
        bs = bs.set(jnp.array([0, 5, 99]))
        assert bool(bs.test(0)) and bool(bs.test(5)) and bool(bs.test(99))
        assert not bool(bs.test(1))
        assert int(bs.count()) == 3

    def test_set_same_word_multiple_bits(self):
        """Regression: several indices in one 32-bit word in a single call."""
        bs = Bitset.create(8, default=False).set(jnp.array([0, 1, 2]))
        mask = np.asarray(bs.to_mask())
        np.testing.assert_array_equal(mask[:4], [True, True, True, False])
        assert int(bs.count()) == 3

    def test_clear_bits(self):
        bs = Bitset.create(64, default=True).set(jnp.array([3, 40]), value=False)
        assert not bool(bs.test(3)) and not bool(bs.test(40))
        assert int(bs.count()) == 62

    def test_count_respects_tail(self):
        bs = Bitset.create(33, default=True)
        assert int(bs.count()) == 33

    def test_from_mask_roundtrip(self, rng):
        mask = rng.random(77) > 0.5
        bs = Bitset.from_mask(jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(bs.to_mask()), mask)
        assert int(bs.count()) == mask.sum()

    def test_flip(self):
        bs = Bitset.create(10, default=False).set(jnp.array([1]))
        flipped = bs.flip()
        assert not bool(flipped.test(1)) and bool(flipped.test(0))

    def test_jit_boundary(self):
        bs = Bitset.from_mask(jnp.array([True, False, True]))

        @jax.jit
        def f(b):
            return b.test(jnp.array([0, 1, 2]))

        np.testing.assert_array_equal(np.asarray(f(bs)), [True, False, True])


class TestSerialize:
    def test_scalar_roundtrip(self):
        buf = io.BytesIO()
        for v in [True, 42, 3.5, "hello"]:
            serialize.serialize_scalar(buf, v)
        buf.seek(0)
        assert serialize.deserialize_scalar(buf) is True
        assert serialize.deserialize_scalar(buf) == 42
        assert serialize.deserialize_scalar(buf) == 3.5
        assert serialize.deserialize_scalar(buf) == "hello"

    def test_array_is_npy_format(self, rng):
        buf = io.BytesIO()
        arr = rng.random((3, 4)).astype(np.float32)
        serialize.serialize_array(buf, arr)
        buf.seek(0)
        loaded = np.load(buf)  # plain numpy can read it
        np.testing.assert_array_equal(loaded, arr)

    def test_tree_roundtrip(self, rng, tmp_path):
        fn = str(tmp_path / "t.bin")
        arrays = {"a": rng.random((2, 2)).astype(np.float32)}
        serialize.save_tree(fn, "test_kind", 3, {"n": 5, "name": "x"}, arrays)
        scalars, loaded = serialize.load_tree(fn, "test_kind", 3)
        assert scalars == {"n": 5, "name": "x"}
        np.testing.assert_array_equal(loaded["a"], arrays["a"])

    def test_version_mismatch(self, tmp_path):
        fn = str(tmp_path / "t.bin")
        serialize.save_tree(fn, "k", 1, {}, {})
        with pytest.raises(ValueError, match="version"):
            serialize.load_tree(fn, "k", 2)

    def test_kind_mismatch(self, tmp_path):
        fn = str(tmp_path / "t.bin")
        serialize.save_tree(fn, "ivf_flat", 1, {}, {})
        with pytest.raises(ValueError, match="expected"):
            serialize.load_tree(fn, "ivf_pq", 1)


class TestValidation:
    """RAFT_EXPECTS-style guards (ref: core/error.hpp RAFT_EXPECTS/RAFT_FAIL)."""

    def test_expects_and_fail(self):
        from raft_tpu.core import validation as v

        v.expects(True, "fine")
        with pytest.raises(v.LogicError):
            v.expects(False, "nope")
        with pytest.raises(v.RaftError):
            v.fail("always")
        # LogicError must stay a ValueError so pre-existing callers keep working
        assert issubclass(v.LogicError, ValueError)

    def test_check_helpers(self, rng):
        from raft_tpu.core import validation as v

        x = rng.random((4, 8)).astype(np.float32)
        v.check_matrix(x, "x")
        v.check_same_cols(x, x)
        v.check_in("a", ("a", "b"))
        v.check_positive(3)
        with pytest.raises(v.LogicError):
            v.check_matrix(x[0], "x")
        with pytest.raises(v.LogicError):
            v.check_matrix(x, "x", min_rows=10)
        with pytest.raises(v.LogicError):
            v.check_matrix(x, "x", dtypes=["int32"])
        with pytest.raises(v.LogicError):
            v.check_same_cols(x, rng.random((4, 9)))
        with pytest.raises(v.LogicError):
            v.check_in("c", ("a", "b"))
        with pytest.raises(v.LogicError):
            v.check_positive(0)

    def test_public_entries_guarded(self, rng):
        from raft_tpu.core import validation as v
        from raft_tpu.distance.pairwise import pairwise_distance
        from raft_tpu.neighbors import brute_force

        x = rng.random((10, 4)).astype(np.float32)
        with pytest.raises(v.LogicError):
            pairwise_distance(x, metric="not-a-metric")
        with pytest.raises(v.LogicError):
            brute_force.knn(x, rng.random((2, 5)).astype(np.float32), 3)
        with pytest.raises(v.LogicError):
            brute_force.knn(x, x, k=11)


class TestFanout:
    """Stream-pool analog: async dispatch fan-out + H2D prefetch
    (ref: core/resource/cuda_stream_pool.hpp; knn_brute_force.cuh:451-485)."""

    def test_async_fanout_matches_sequential(self, rng):
        from raft_tpu.core.fanout import async_fanout, row_batches

        f = jax.jit(lambda a: jnp.sum(a * a, axis=1))
        x = rng.random((1000, 16)).astype(np.float32)
        batches = [(b,) for b in row_batches(jnp.asarray(x), 256)]
        assert [b[0].shape[0] for b in batches] == [256, 256, 256, 232]
        outs = async_fanout(f, batches)
        got = np.concatenate([np.asarray(o) for o in outs])
        np.testing.assert_allclose(got, (x * x).sum(1), rtol=1e-5)

    def test_prefetch_to_device(self, rng):
        from raft_tpu.core.fanout import prefetch_to_device

        chunks = [rng.random((8, 4)).astype(np.float32) for _ in range(5)]
        out = list(prefetch_to_device(chunks, lookahead=2))
        assert len(out) == 5
        for c, o in zip(chunks, out):
            assert isinstance(o, jax.Array)
            np.testing.assert_array_equal(np.asarray(o), c)


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_compile_cache_location(tmp_path, env_dir):
    """The persistent compile cache lives where JAX_COMPILATION_CACHE_DIR
    says, and otherwise at the fixed <checkout>/.jax_cache (import-time
    config, so each branch runs in a fresh interpreter)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "RAFT_TPU_NO_COMPILE_CACHE")}
    env["JAX_PLATFORMS"] = "cpu"
    want = os.path.join(root, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, "-c",
         "import raft_tpu, jax; print(jax.config.jax_compilation_cache_dir)"],
        cwd=tmp_path, env={**env, "PYTHONPATH": root},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == want
