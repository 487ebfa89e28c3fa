"""CAGRA + NN-descent: recall gates vs brute force, graph invariants,
serialization (mirrors cpp/test/neighbors/ann_cagra/ + ann_nn_descent/
recall thresholds and pylibraft test_cagra)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.core.bitset import Bitset
from raft_tpu.neighbors import brute_force, cagra, nn_descent
from raft_tpu.random import make_blobs
from raft_tpu.stats import neighborhood_recall


@pytest.fixture(scope="module")
def data():
    """Clustered dataset with in-distribution queries (perturbed data rows) —
    the reference's ANN suites also query from the data distribution
    (cpp/test/neighbors/ann_cagra uses uniform data + uniform queries)."""
    key = jax.random.PRNGKey(0)
    x, _, _ = make_blobs(key, 4000, 32, n_clusters=20, cluster_std=2.0)
    x = np.asarray(x)
    rng = np.random.default_rng(7)
    q = x[rng.choice(x.shape[0], 48, replace=False)]
    q = q + rng.normal(0, 1.0, q.shape).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def built(data):
    x, _ = data
    params = cagra.IndexParams(
        intermediate_graph_degree=48, graph_degree=24, build_algo="brute_force"
    )
    return cagra.build(params, x)


def test_graph_invariants(built, data):
    x, _ = data
    n = x.shape[0]
    g = np.asarray(built.graph)
    assert g.shape == (n, 24)
    assert (g >= 0).all() and (g < n).all()
    # no self edges, no duplicate edges within a row
    assert (g != np.arange(n)[:, None]).all()
    for row in g[:100]:
        assert len(set(row.tolist())) == len(row)


@pytest.mark.parametrize("itopk,min_recall", [(32, 0.85), (64, 0.95)])
def test_recall_vs_bruteforce(built, data, itopk, min_recall):
    x, q = data
    k = 10
    _, gt = brute_force.knn(x, q, k)
    _, idx = cagra.search(cagra.SearchParams(itopk_size=itopk), built, q, k)
    r = float(neighborhood_recall(np.asarray(idx), np.asarray(gt)))
    assert r >= min_recall, (itopk, r)


def test_nn_descent_build_algo(data):
    x, q = data
    params = cagra.IndexParams(
        intermediate_graph_degree=48,
        graph_degree=24,
        build_algo="nn_descent",
        nn_descent_niter=30,
    )
    index = cagra.build(params, x)
    k = 10
    _, gt = brute_force.knn(x, q, k)
    _, idx = cagra.search(cagra.SearchParams(itopk_size=64), index, q, k)
    r = float(neighborhood_recall(np.asarray(idx), np.asarray(gt)))
    assert r >= 0.85, r


def test_ivf_pq_build_algo(data):
    x, q = data
    params = cagra.IndexParams(
        intermediate_graph_degree=48, graph_degree=24, build_algo="ivf_pq"
    )
    index = cagra.build(params, x)
    k = 10
    _, gt = brute_force.knn(x, q, k)
    _, idx = cagra.search(cagra.SearchParams(itopk_size=64), index, q, k)
    r = float(neighborhood_recall(np.asarray(idx), np.asarray(gt)))
    assert r >= 0.8, r


def test_graph_source_candidates_do_not_depend_on_row_order(
    data, monkeypatch
):
    """The build searches the rows list by list; every row gets the
    candidates a search in row order, in tiles of the same size, gives."""
    from raft_tpu.core.resources import ensure
    from raft_tpu.neighbors import ivf_pq

    x, _ = data
    params = cagra.IndexParams(
        intermediate_graph_degree=48, graph_degree=24, build_algo="ivf_pq"
    )
    ip, sp, k = cagra._graph_build_ivf_pq_params(params, *x.shape)
    idx = ivf_pq.build(ip, x)
    tile = 1000
    monkeypatch.setattr(cagra, "_graph_build_qtile", lambda *_: tile)
    order = cagra._list_order(idx.list_index)
    assert sorted(order.tolist()) == list(range(x.shape[0]))
    assert not np.array_equal(order, np.arange(x.shape[0]))
    got = cagra._graph_source_candidates(idx, sp, jnp.asarray(x), k, ensure(None))
    want = np.concatenate([
        np.asarray(ivf_pq.search(sp, idx, x[s:s + tile], k)[1])
        for s in range(0, x.shape[0], tile)
    ])
    np.testing.assert_array_equal(np.asarray(got), want)


def test_inner_product_metric(data):
    x, q = data
    params = cagra.IndexParams(
        metric="inner_product",
        intermediate_graph_degree=48,
        graph_degree=24,
        build_algo="brute_force",
    )
    index = cagra.build(params, x)
    k = 10
    _, gt = brute_force.knn(x, q, k, metric="inner_product")
    d, idx = cagra.search(cagra.SearchParams(itopk_size=64), index, q, k)
    r = float(neighborhood_recall(np.asarray(idx), np.asarray(gt)))
    assert r >= 0.85, r
    # returned distances are true inner products (descending)
    d = np.asarray(d)
    assert (np.diff(d, axis=1) <= 1e-4).all()


def test_random_samplings_rescue_disconnected_graph(built, data):
    """Out-of-distribution queries on a cluster-disconnected graph depend on
    seed luck; num_random_samplings (ref search_params) buys recall back."""
    x, _ = data
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (48, 32)) * 4.0)
    _, gt = brute_force.knn(x, q, 10)
    rs = []
    for ns in (1, 8):
        _, idx = cagra.search(
            cagra.SearchParams(itopk_size=64, num_random_samplings=ns),
            built, q, 10,
        )
        rs.append(float(neighborhood_recall(np.asarray(idx), np.asarray(gt))))
    assert rs[1] >= rs[0]
    assert rs[1] >= 0.9, rs


def test_bitset_prefilter(built, data):
    x, q = data
    n = x.shape[0]
    mask = np.arange(n) % 2 == 1
    bs = Bitset.from_mask(jnp.asarray(mask))
    _, idx = cagra.search(
        cagra.SearchParams(itopk_size=64), built, q, 10, sample_filter=bs
    )
    idx = np.asarray(idx)
    assert (idx[idx >= 0] % 2 == 1).all()
    assert (idx >= 0).mean() > 0.5  # filter still leaves plenty of hits


def test_sparse_bitset_prefilter(built, data):
    """A very sparse filter must still fill k result slots: traversal runs
    unfiltered while the result list collects only filter-passing hits
    (regression: post-hoc filtering returned mostly −1)."""
    x, q = data
    n = x.shape[0]
    k = 5
    mask = np.zeros(n, bool)
    allowed = np.arange(0, n, 97)  # ~1% of points
    mask[allowed] = True
    bs = Bitset.from_mask(jnp.asarray(mask))
    _, idx = cagra.search(
        cagra.SearchParams(itopk_size=64, max_iterations=48),
        built, q, k, sample_filter=bs,
    )
    idx = np.asarray(idx)
    assert (idx[idx >= 0] % 97 == 0).all()
    # beam passes near many allowed points over 48 iterations
    assert (idx >= 0).mean() > 0.6, (idx >= 0).mean()
    # no duplicate ids within a row among valid entries
    for row in idx:
        v = row[row >= 0]
        assert len(set(v.tolist())) == len(v)


def test_from_graph_and_serialization(built, data, tmp_path):
    x, q = data
    fn = str(tmp_path / "cagra.idx")
    cagra.save(fn, built)
    loaded = cagra.load(fn)
    d1, i1 = cagra.search(cagra.SearchParams(), built, q, 5)
    d2, i2 = cagra.search(cagra.SearchParams(), loaded, q, 5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # dataset-less save + from_graph reconstruction
    fn2 = str(tmp_path / "cagra_nodata.idx")
    cagra.save(fn2, built, include_dataset=False)
    loaded2 = cagra.load(fn2, dataset=x)
    _, i3 = cagra.search(cagra.SearchParams(), loaded2, q, 5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i3))
    rebuilt = cagra.from_graph(built.metric, x, built.graph)
    _, i4 = cagra.search(cagra.SearchParams(), rebuilt, q, 5)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i4))


def test_optimize_prunes_to_degree(data):
    x, _ = data
    g = nn_descent.build_exact(x, 32).graph
    out = cagra.optimize(g, 16)
    out = np.asarray(out)
    assert out.shape == (x.shape[0], 16)
    assert (out >= 0).all()
    for row in out[:50]:
        assert len(set(row.tolist())) == len(row)


# --------------------------------------------------------------------------
# nn_descent standalone (ref: cpp/test/neighbors/ann_nn_descent/)
# --------------------------------------------------------------------------

def test_nn_descent_graph_recall(data):
    x, _ = data
    deg = 24
    params = nn_descent.IndexParams(
        graph_degree=deg, intermediate_graph_degree=36, max_iterations=30
    )
    idx = nn_descent.build(params, x)
    exact = nn_descent.build_exact(x, deg)
    r = float(neighborhood_recall(np.asarray(idx.graph), np.asarray(exact.graph)))
    assert r >= 0.85, r
    # graph rows: no self, no dups, valid ids
    g = np.asarray(idx.graph)
    n = x.shape[0]
    assert (g >= 0).all() and (g < n).all()
    assert (g != np.arange(n)[:, None]).all()
    for row in g[:100]:
        assert len(set(row.tolist())) == len(row)
    # distances are consistent with the ids
    d = np.asarray(idx.distances[:64])
    xx = np.asarray(x)
    want = ((xx[:64, None, :] - xx[g[:64]]) ** 2).sum(-1)
    np.testing.assert_allclose(d, want, rtol=1e-3, atol=1e-2)


def test_nn_descent_exact_no_self(data):
    x, _ = data
    idx = nn_descent.build_exact(x, 8)
    g = np.asarray(idx.graph)
    assert (g != np.arange(x.shape[0])[:, None]).all()


class TestEntryPoints:
    """Coarse entry-point seeding (round-4 TPU-first addition): the beam
    starts from the nearest coarse centroids' representative rows instead
    of navigating from random seeds."""

    def test_build_creates_entry_table(self, built, data):
        x, _ = data
        assert built.entry_centers is not None
        c = built.entry_centers.shape[0]
        assert built.entry_ids.shape == (c,)
        ids = np.asarray(built.entry_ids)
        assert ((ids >= 0) & (ids < x.shape[0])).all()
        # each representative is the dataset row nearest its centroid
        cen = np.asarray(built.entry_centers)
        d_rep = ((np.asarray(x)[ids] - cen) ** 2).sum(1)
        rng = np.random.default_rng(0)
        probe = rng.choice(x.shape[0], 200, replace=False)
        d_probe = (
            (np.asarray(x)[probe][None] - cen[:, None]) ** 2
        ).sum(-1).min(1)
        assert (d_rep <= d_probe + 1e-4).all()

    def test_entry_points_zero_disables(self, data):
        x, _ = data
        idx = cagra.build(
            cagra.IndexParams(
                intermediate_graph_degree=48, graph_degree=24,
                build_algo="brute_force", entry_points=0,
            ), x,
        )
        assert idx.entry_centers is None
        # search falls back to random seeding and still works
        _, ids = cagra.search(cagra.SearchParams(), idx, x[:8], 5)
        assert np.asarray(ids).shape == (8, 5)

    def test_entry_seeded_recall_with_few_iterations(self, built, data):
        """The economics claim: entry seeding reaches high recall in a
        handful of iterations, where random seeding needs the full
        navigation budget."""
        x, q = data
        k = 10
        _, gt = brute_force.knn(x, q, k)
        sp = cagra.SearchParams(
            itopk_size=16, search_width=1, max_iterations=6,
            num_entry_centers=16,
        )
        _, ids = cagra.search(sp, built, q, k)
        r = float(neighborhood_recall(np.asarray(ids), np.asarray(gt)))
        assert r >= 0.9, r

    def test_entry_table_serialization_roundtrip(self, built, tmp_path):
        p = str(tmp_path / "cagra_entries.bin")
        cagra.save(p, built)
        back = cagra.load(p)
        np.testing.assert_array_equal(
            np.asarray(back.entry_ids), np.asarray(built.entry_ids))
        np.testing.assert_allclose(
            np.asarray(back.entry_centers), np.asarray(built.entry_centers))
        # and a file without entries still loads (backward compat)
        idx2 = cagra.Index(built.metric, built.dataset, built.graph)
        p2 = str(tmp_path / "cagra_noentries.bin")
        cagra.save(p2, idx2)
        back2 = cagra.load(p2)
        assert back2.entry_centers is None

    def test_entry_seeding_respects_filter(self, built, data):
        """Filtered search with entry seeds: filtered-out rows may still
        route the walk but must never appear in results."""
        x, q = data
        n = x.shape[0]
        mask = np.zeros(n, bool); mask[::2] = True  # only even ids pass
        bs = Bitset.from_mask(jnp.asarray(mask))
        sp = cagra.SearchParams(
            itopk_size=32, search_width=1, max_iterations=8,
            num_entry_centers=16,
        )
        _, ids = cagra.search(sp, built, q, 10, sample_filter=bs)
        ids = np.asarray(ids)
        assert ((ids % 2 == 0) | (ids == -1)).all()


class TestBatchNNDescent:
    """Out-of-core NN-descent (ref nn_descent_batch.cuh): clustered
    per-batch GNND + global merge; CAGRA graph builds at sizes the
    in-memory path cannot hold."""

    def test_batch_graph_recall(self):
        key = jax.random.PRNGKey(21)
        x, _, _ = make_blobs(key, 6000, 24, n_clusters=32, cluster_std=2.0)
        x = np.asarray(x)
        p = nn_descent.IndexParams(
            graph_degree=24, intermediate_graph_degree=36, max_iterations=10
        )
        # max_cluster_rows forces ~6 overlapping clusters (the out-of-core
        # path) even though the data would fit in memory
        g = nn_descent.build_batch(p, x, max_cluster_rows=2048)
        gi = np.asarray(g.graph)
        n = x.shape[0]
        assert gi.shape == (n, 24)
        assert (gi < n).all()
        assert (gi != np.arange(n)[:, None]).all()
        _, gt = brute_force.knn(x, x, 25)
        gt = np.asarray(gt)[:, 1:]
        sub = range(0, n, 10)
        rec = np.mean([
            len(np.intersect1d(gi[i], gt[i])) / 24 for i in sub
        ])
        assert rec >= 0.8, rec
        # distances are the true metric values for the reported neighbors
        gd = np.asarray(g.distances)
        i0 = gi[0]
        want = ((x[0][None] - x[i0]) ** 2).sum(-1)
        np.testing.assert_allclose(gd[0], want, rtol=1e-3, atol=1e-3)

    def test_cagra_build_algo_batch(self):
        key = jax.random.PRNGKey(22)
        x, _, _ = make_blobs(key, 5000, 24, n_clusters=25, cluster_std=2.0)
        x = np.asarray(x)
        rng = np.random.default_rng(3)
        q = x[rng.choice(x.shape[0], 48, replace=False)] + 0.01
        idx = cagra.build(
            cagra.IndexParams(
                intermediate_graph_degree=36, graph_degree=24,
                build_algo="nn_descent_batch",
            ), x,
        )
        _, gt = brute_force.knn(x, q, 10)
        _, ids = cagra.search(cagra.SearchParams(itopk_size=32), idx, q, 10)
        r = float(neighborhood_recall(np.asarray(ids), np.asarray(gt)))
        assert r >= 0.9, r

    def test_batch_rejects_inner_product(self):
        p = nn_descent.IndexParams(metric="inner_product")
        with pytest.raises(ValueError, match="L2"):
            nn_descent.build_batch(p, np.zeros((100, 8), np.float32))
