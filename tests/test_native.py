"""Native C++ core: build, resources/workspace, npy interop with numpy,
logger callback, interruptible (mirrors cpp/test/core/ — resources,
serialization, interruptible suites)."""

import os

import numpy as np
import pytest

from raft_tpu.core import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain unavailable"
)


def test_resources_workspace_lifecycle():
    res = native.NativeResources(workspace_limit_bytes=1 << 20)
    p = res.workspace_alloc(1000)
    assert res.workspace_used >= 1000
    # shallow copy shares the arena (reference resources semantics)
    res2 = res.copy()
    assert res2.workspace_used == res.workspace_used
    res.workspace_free(p)
    assert res.workspace_used == 0
    assert res.workspace_high_water >= 1000


def test_workspace_limit_enforced():
    res = native.NativeResources(workspace_limit_bytes=1024)
    with pytest.raises(MemoryError):
        res.workspace_alloc(4096)


def test_npy_write_numpy_reads(tmp_path, rng):
    for arr in (
        rng.random((7, 5)).astype(np.float32),
        rng.integers(0, 255, (4, 3, 2)).astype(np.uint8),
        rng.integers(-100, 100, 11).astype(np.int64),
        rng.random(6).astype(np.float64),
    ):
        p = str(tmp_path / "a.npy")
        native.npy_write(p, arr)
        back = np.load(p)
        np.testing.assert_array_equal(back, arr)


def test_numpy_write_native_reads(tmp_path, rng):
    for arr in (
        rng.random((9, 2)).astype(np.float32),
        rng.integers(0, 1000, (3, 3)).astype(np.int32),
    ):
        p = str(tmp_path / "b.npy")
        np.save(p, arr)
        back = native.npy_read(p)
        np.testing.assert_array_equal(back, arr)
        assert back.dtype == arr.dtype


def test_logger_callback():
    got = []
    native.log_set_callback(lambda lvl, msg: got.append((lvl, msg)))
    native.log_set_level(4)  # debug
    native.log(2, "warn message")
    native.log(5, "trace filtered")  # above level → dropped
    native.log_set_callback(None)
    assert (2, "warn message") in got
    assert all("trace" not in m for _, m in got)


def test_interruptible():
    tok = native.InterruptibleToken()
    assert not tok.cancelled
    tok.check()  # no-op
    tok.cancel()
    assert tok.cancelled
    with pytest.raises(InterruptedError):
        tok.check()
    # flag cleared by the failed check (reference behavior)
    assert not tok.cancelled
    tok.check()


def test_refine_host_matches_numpy(rng):
    """Native threaded refine (raft_runtime-style entry point) vs the jax
    device refine."""
    from raft_tpu.neighbors.refine import refine

    x = rng.random((500, 24)).astype(np.float32)
    q = rng.random((40, 24)).astype(np.float32)
    cand = rng.integers(-1, 500, (40, 30)).astype(np.int32)
    for metric in ("sqeuclidean", "euclidean", "inner_product", "cosine"):
        vd, idd = refine(x, q, cand, 5, metric=metric, host=False)
        vh, idh = native.refine_host(x, q, cand, 5, metric)
        np.testing.assert_allclose(np.asarray(vd), vh, rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(idd), idh)


def test_pack_list_layout_split(rng):
    """Native list layout: shards appear for oversized lists, slots dense."""
    labels = np.concatenate([np.zeros(100, np.int64), np.ones(10, np.int64)])
    slot, lst, cmap, cap = native.pack_list_layout(labels, 2, 32)
    assert cap == 32
    # list 0 (100 rows, max_cap 32) → 4 shards: ids {0, 2, 3, 4}
    assert len(cmap) == 5
    assert list(cmap) == [0, 1, 0, 0, 0]
    counts = np.bincount(lst, minlength=5)
    assert counts.tolist() == [32, 10, 32, 32, 4]
    # slots dense per shard
    for l in range(5):
        s = np.sort(slot[lst == l])
        np.testing.assert_array_equal(s, np.arange(len(s)))


def test_resources_native_backing():
    from raft_tpu.core.resources import Resources

    res = Resources(workspace_limit_bytes=1 << 20)
    nat = res.native
    if nat is None:
        pytest.skip("no native toolchain")
    p = nat.workspace_alloc(1024)
    assert nat.workspace_used >= 1024
    nat.workspace_free(p)
    assert res.native is nat  # cached on the registry


def test_header_compile_surface():
    """Every public C++ header compiles standalone (ref: the reference's
    ext_headers targets, cpp/test/CMakeLists.txt:204-205)."""
    import os
    import shutil
    import subprocess

    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no native toolchain")
    cpp = os.path.join(os.path.dirname(os.path.dirname(__file__)), "cpp")
    out = subprocess.run(
        ["make", "-C", cpp, "check-headers"], capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_native_core_units():
    """span / memory_type / mdarray / mdbuffer behavioral tests (ref:
    cpp/test/core/ gtest suites) via the dependency-free assert runner."""
    import os
    import shutil
    import subprocess

    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no native toolchain")
    cpp = os.path.join(os.path.dirname(os.path.dirname(__file__)), "cpp")
    out = subprocess.run(
        ["make", "-C", cpp, "check-core"], capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "core_test ok" in out.stdout


def test_native_knn_host(rng):
    """Native brute-force kNN matches numpy exactly (groundtruth path)."""
    from raft_tpu.core import native

    if not native.available():
        pytest.skip("no native toolchain")
    x = rng.standard_normal((500, 24)).astype(np.float32)
    q = rng.standard_normal((40, 24)).astype(np.float32)
    d, i = native.knn_host(x, q, 5)
    d2 = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1)[:, :5]
    np.testing.assert_array_equal(i, want)
    np.testing.assert_allclose(
        d, np.take_along_axis(d2, want, 1), rtol=1e-4, atol=1e-4
    )
    # inner product: largest similarity first, similarities returned as-is
    dip, iip = native.knn_host(x, q, 5, metric="inner_product")
    ip = q @ x.T
    want_ip = np.argsort(-ip, axis=1)[:, :5]
    np.testing.assert_array_equal(iip, want_ip)
    np.testing.assert_allclose(
        dip, np.take_along_axis(ip, want_ip, 1), rtol=1e-4, atol=1e-4
    )


def test_native_select_k_host(rng):
    from raft_tpu.core import native

    if not native.available():
        pytest.skip("no native toolchain")
    s = rng.standard_normal((30, 200)).astype(np.float32)
    v, i = native.select_k_host(s, 7)
    want = np.sort(s, axis=1)[:, :7]
    np.testing.assert_allclose(v, want, rtol=1e-6)
    np.testing.assert_allclose(np.take_along_axis(s, i, 1), v, rtol=1e-6)
    v2, i2 = native.select_k_host(s, 7, select_min=False)
    np.testing.assert_allclose(v2, np.sort(s, 1)[:, ::-1][:, :7], rtol=1e-6)
    np.testing.assert_allclose(np.take_along_axis(s, i2, 1), v2, rtol=1e-6)
    # NaN scores rank worst instead of corrupting the sort
    s_nan = s.copy()
    s_nan[:, 0] = np.nan
    v3, i3 = native.select_k_host(s_nan, 7)
    assert not np.isnan(v3).any() and (i3 != 0).all()


def test_native_pairwise_distance_host(rng):
    """(ref: raft_runtime/distance/pairwise_distance.hpp role)"""
    x = rng.random((60, 12), np.float32)
    y = rng.random((40, 12), np.float32)
    d = native.pairwise_distance_host(x, y)
    want = ((x[:, None] - y[None]) ** 2).sum(-1)
    np.testing.assert_allclose(d, want, rtol=1e-4, atol=1e-5)
    dc = native.pairwise_distance_host(x, y, metric="cosine")
    nx = x / np.linalg.norm(x, axis=1, keepdims=True)
    ny = y / np.linalg.norm(y, axis=1, keepdims=True)
    np.testing.assert_allclose(dc, 1.0 - nx @ ny.T, rtol=1e-4, atol=1e-5)


def test_native_kmeans_fit_host(rng):
    """(ref: raft_runtime/cluster/kmeans.hpp fit role) — labels/inertia
    must be self-consistent with the returned centers."""
    x = np.concatenate(
        [rng.normal(c, 0.1, (50, 4)) for c in (0.0, 5.0, 10.0)]
    ).astype(np.float32)
    init = x[[0, 50, 100]].copy()
    c, lab, inertia = native.kmeans_fit_host(x, init, n_iters=10)
    d = ((x[:, None] - c[None]) ** 2).sum(-1)
    np.testing.assert_array_equal(lab, d.argmin(1))
    np.testing.assert_allclose(inertia, d.min(1).sum(), rtol=1e-4)
    # three tight blobs: near-perfect clustering
    assert inertia < 50.0


def test_native_rmat_host():
    """(ref: raft_runtime/random/rmat_rectangular_generator.hpp role) —
    in-range rectangular edges with power-law row skew; deterministic per
    seed."""
    r, c = native.rmat_host(8, 6, 4000, seed=7)
    assert r.min() >= 0 and r.max() < 256
    assert c.min() >= 0 and c.max() < 64
    counts = np.bincount(r, minlength=256)
    assert counts.max() > 4000 / 256 * 3  # heavy head vs uniform
    r2, c2 = native.rmat_host(8, 6, 4000, seed=7)
    np.testing.assert_array_equal(r, r2)
    np.testing.assert_array_equal(c, c2)


def test_native_ann_round_trip():
    """ANN-index C ABI round trip: build/search/serialize every index kind
    purely through c_api.h — the raft_runtime/neighbors role (ref:
    raft_runtime/neighbors/ivf_pq.hpp:32-92, cagra.hpp:30-80)."""
    import os
    import shutil
    import subprocess

    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no native toolchain")
    cpp = os.path.join(os.path.dirname(os.path.dirname(__file__)), "cpp")
    out = subprocess.run(
        ["make", "-C", cpp, "check-ann"], capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "checks passed" in out.stdout


def test_native_ann_python_bindings(rng, tmp_path):
    """NativeAnnIndex over the ANN C ABI: build/search/save/load from
    Python, cross-checked against the JAX engines' exact groundtruth —
    two independent implementations of the same index semantics."""
    from raft_tpu.core import native
    from raft_tpu.neighbors import brute_force
    from raft_tpu.stats import neighborhood_recall

    if not native.available():
        pytest.skip("no native toolchain")
    x = (rng.random((4000, 32)).astype(np.float32) * 4.0)
    q = x[:50] + 0.01
    _, gt = brute_force.knn(x, q, 10)
    gt = np.asarray(gt)

    flat = native.NativeAnnIndex.ivf_flat(x, 32)
    assert flat.info["kind"] == "ivf_flat" and flat.info["n_lists"] == 32
    _, fi = flat.search(q, 10, n_probes=32)      # all lists -> exact
    assert float(neighborhood_recall(fi, gt)) >= 0.999

    pq = native.NativeAnnIndex.ivf_pq(x, 32, pq_dim=8)
    _, ci = pq.search(q, 100, n_probes=16)       # ADC pool + exact refine
    _, pi = native.refine_host(x, q, ci, 10)
    assert float(neighborhood_recall(pi, gt)) >= 0.9

    cg = native.NativeAnnIndex.cagra(x, graph_degree=24)
    _, gi = cg.search(q, 10, itopk=64)
    assert float(neighborhood_recall(gi, gt)) >= 0.9

    fn = str(tmp_path / "flat.native.idx")
    flat.save(fn)
    flat2 = native.NativeAnnIndex.load(fn)
    _, fi2 = flat2.search(q, 10, n_probes=32)
    np.testing.assert_array_equal(fi, fi2)


def test_native_eps_neighbors(rng):
    from raft_tpu.core import native

    if not native.available():
        pytest.skip("no native toolchain")
    x = rng.random((500, 8)).astype(np.float32)
    q = x[:7]
    eps = 0.6
    adj, vd = native.eps_neighbors_host(x, q, eps)
    d2 = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    np.testing.assert_array_equal(adj, d2 <= eps * eps)
    np.testing.assert_array_equal(vd, (d2 <= eps * eps).sum(1))


@pytest.mark.parametrize("edited", ["src/a.cc", "include/core/b.hpp", "Makefile"])
def test_native_rebuild_inputs(tmp_path, edited):
    """A source, a header or a Makefile newer than the library rebuilds it."""
    from raft_tpu.core.native import _stale

    so = tmp_path / "lib.so"
    for rel in ("src/a.cc", "include/core/b.hpp", "Makefile", "lib.so"):
        f = tmp_path / rel
        f.parent.mkdir(parents=True, exist_ok=True)
        f.write_text("")
        os.utime(f, (100, 100))
    assert not _stale(str(tmp_path), str(so))
    os.utime(tmp_path / edited, (200, 200))
    assert _stale(str(tmp_path), str(so))
