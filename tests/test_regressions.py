"""Regression tests for review findings."""

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.distance import pairwise_distance
from raft_tpu.ops import matrix
from raft_tpu.stats import silhouette_score


def test_correlation_constant_rows():
    """Constant rows must not blow up correlation distance."""
    x = np.array([[1.0, 1.0, 1.0], [0.5, 1.0, 2.0]], np.float32)
    d = np.asarray(pairwise_distance(x, x, metric="correlation"))
    assert np.all(np.isfinite(d))
    assert np.all(d >= -1e-5) and np.all(d <= 2.0 + 1e-5)


def test_silhouette_empty_cluster():
    x = np.array([[0.0, 0], [0.1, 0], [5.0, 5], [5.1, 5]], np.float32)
    labels = np.array([0, 0, 1, 1], np.int32)
    s2 = float(silhouette_score(x, labels, n_clusters=2))
    s3 = float(silhouette_score(x, labels, n_clusters=3))  # cluster 2 empty
    assert s2 == pytest.approx(s3, abs=1e-5)
    assert s2 > 0.9


def test_select_k_large_ints_exact():
    """Integers above 2^24 must not lose exactness to float32."""
    x = np.array([[16777217, 16777216, 3]], np.int32)
    vals, idx = matrix.select_k(x, 1, select_min=False)
    assert int(vals[0, 0]) == 16777217
    assert int(idx[0, 0]) == 0
    vals, idx = matrix.select_k(x, 2, select_min=True)
    assert int(vals[0, 0]) == 3 and int(vals[0, 1]) == 16777216


def test_comms_prod_with_negatives():
    from jax.sharding import PartitionSpec as P

    from raft_tpu.comms import local_comms
    from jax import shard_map

    comms = local_comms(8)

    def body(x):
        return comms.allreduce(x[0], op="prod")[None]

    f = shard_map(
        body, mesh=comms.mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False,
    )
    x = jnp.array([-2.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 3.0])
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, -6.0, rtol=1e-5)
    # with a zero anywhere, product is zero
    x0 = x.at[3].set(0.0)
    np.testing.assert_allclose(np.asarray(f(x0)), 0.0, atol=1e-12)


def test_sharded_knn_inner_product():
    from raft_tpu.comms import local_comms
    from raft_tpu.comms.distributed import sharded_knn
    from raft_tpu.neighbors import brute_force
    from raft_tpu.stats import neighborhood_recall

    rng = np.random.default_rng(1)
    x = rng.random((160, 8)).astype(np.float32)
    q = rng.random((12, 8)).astype(np.float32)
    comms = local_comms(8)
    dv, di = sharded_knn(comms, x, q, 5, metric="inner_product")
    sv, si = brute_force.knn(x, q, 5, metric="inner_product")
    assert float(neighborhood_recall(np.asarray(di), np.asarray(si))) >= 0.999


def test_ivf_filtered_ids_never_leak():
    """A sparse bitset that leaves fewer than k candidates must yield -1 ids
    with +inf distance, never the real id of a filtered-out vector
    (code-review finding: filtered candidates kept real ids)."""
    from raft_tpu.core.bitset import Bitset
    from raft_tpu.neighbors import ivf_flat

    rng = np.random.default_rng(0)
    x = rng.random((500, 16)).astype(np.float32)
    index = ivf_flat.build(ivf_flat.IndexParams(n_lists=4, kmeans_n_iters=4), x)
    mask = np.zeros(500, bool)
    mask[:5] = True  # only 5 allowed ids, k=10
    bs = Bitset.from_mask(jnp.asarray(mask))
    d, i = ivf_flat.search(
        ivf_flat.SearchParams(n_probes=4), index, x[:8], 10, sample_filter=bs
    )
    d, i = np.asarray(d), np.asarray(i)
    assert set(i[i >= 0].ravel()) <= set(range(5))
    assert np.isinf(d[i < 0]).all()


def test_kmeans_cosine_metric_respected():
    """KMeansParams.metric='cosine' runs spherical kmeans (code-review
    finding: metric field was silently ignored)."""
    from raft_tpu.cluster import kmeans

    rng = np.random.default_rng(0)
    # two directions, different magnitudes — cosine sees 2 clusters
    a = rng.normal(0, 0.01, (50, 8)).astype(np.float32) + np.eye(8)[0] * 1.0
    b = rng.normal(0, 0.01, (50, 8)).astype(np.float32) + np.eye(8)[1] * 1.0
    x = np.concatenate([a * rng.uniform(0.5, 5.0, (50, 1)), b * rng.uniform(0.5, 5.0, (50, 1))])
    params = kmeans.KMeansParams(n_clusters=2, metric="cosine", seed=0)
    c, inertia, _ = kmeans.fit(params, x)
    labels = np.asarray(kmeans.predict(c, x, metric="cosine"))
    assert len(set(labels[:50])) == 1 and len(set(labels[50:])) == 1
    assert labels[0] != labels[-1]
    # centers on unit sphere
    np.testing.assert_allclose(np.linalg.norm(np.asarray(c), axis=1), 1.0, atol=1e-4)


def test_kmeans_init_array_validation():
    from raft_tpu.cluster import kmeans

    with np.testing.assert_raises(ValueError):
        kmeans.fit(kmeans.KMeansParams(n_clusters=2, init="array"), np.ones((10, 3)))


def test_kmeans_balanced_hierarchical_empty_meso():
    """Hierarchical fit must not crash when mesoclusters end up empty
    (code-review finding: AssertionError on empty mesocluster)."""
    from raft_tpu.cluster import kmeans_balanced

    rng = np.random.default_rng(0)
    # tiny tight blob + enough rows to trigger the hierarchical path
    x = np.concatenate(
        [rng.normal(0, 0.001, (2000, 4)), rng.normal(100, 0.001, (2000, 4))]
    ).astype(np.float32)
    params = kmeans_balanced.KMeansBalancedParams(
        n_iters=4, mesocluster_threshold=8, seed=0
    )
    centers = kmeans_balanced.fit(params, x, 300)
    assert centers.shape == (300, 4)
    assert np.isfinite(np.asarray(centers)).all()


def test_headroom_flag_survives_save_load(tmp_path):
    """conservative_memory_allocation's headroom policy must round-trip
    serialization (ref: the reference serializes the flag,
    ivf_pq_serialize.cuh:64 / ivf_flat_serialize.cuh:66 — ADVICE r2)."""
    import jax
    import numpy as np
    from raft_tpu.neighbors import ivf_flat, ivf_pq
    from raft_tpu.random import make_blobs

    key = jax.random.PRNGKey(7)
    x, _, _ = make_blobs(key, 1500, 16, n_clusters=8)
    x = np.asarray(x)
    for mod, params in (
        (ivf_pq, ivf_pq.IndexParams(
            n_lists=8, pq_dim=8, kmeans_n_iters=3,
            conservative_memory_allocation=True)),
        (ivf_flat, ivf_flat.IndexParams(
            n_lists=8, kmeans_n_iters=3,
            conservative_memory_allocation=True)),
    ):
        index = mod.build(params, x)
        assert index.headroom is False
        path = str(tmp_path / f"{mod.__name__.split('.')[-1]}.idx")
        mod.save(path, index)
        loaded = mod.load(path)
        assert loaded.headroom is False
