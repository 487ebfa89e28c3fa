"""Online compaction: shadow rebuilds, quality gate, and the churn soak.

The contract under test is ISSUE 7's: a served ``MutableIndex`` under
sustained upsert/delete churn must stay bounded — side-buffer rows and
live index bytes flat, ids stable across every hot-swap, concurrent
readers never erroring, zero post-warmup hot-path recompiles — while a
failed pass (quality gate, memory budget) aborts cleanly instead of
degrading serving.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import serve
from raft_tpu.neighbors import brute_force, cagra, ivf_flat, ivf_pq
from raft_tpu.serve.compactor import CompactionPolicy, Compactor
from raft_tpu.stats.metrics import recall_at_k

N, D = 400, 24


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(11)
    x = rng.standard_normal((N, D)).astype(np.float32)
    q = rng.standard_normal((16, D)).astype(np.float32)
    return x, q


def _build(kind: str, x: np.ndarray) -> serve.MutableIndex:
    if kind == "brute_force":
        return serve.MutableIndex(brute_force.build(x))
    if kind == "ivf_flat":
        idx = ivf_flat.build(ivf_flat.IndexParams(n_lists=16), x)
        return serve.MutableIndex(
            idx, search_params=ivf_flat.SearchParams(n_probes=16)
        )
    if kind in ("ivf_pq", "ivf_pq_refined"):
        idx = ivf_pq.build(
            ivf_pq.IndexParams(n_lists=16, pq_dim=24, pq_bits=8), x
        )
        return serve.MutableIndex(
            idx, search_params=ivf_pq.SearchParams(n_probes=16),
            refine_dataset=jnp.asarray(x) if kind == "ivf_pq_refined" else None,
        )
    idx = cagra.build(cagra.IndexParams(graph_degree=32), x)
    return serve.MutableIndex(
        idx, search_params=cagra.SearchParams(itopk_size=128)
    )


# compacted indexes answer through the rebuilt main structure; the PQ
# code and the beam search re-approximate, so their floors are laxer
_RECALL_FLOOR = {
    "brute_force": 1.0,
    "ivf_flat": 0.95,
    "ivf_pq": 0.8,
    "ivf_pq_refined": 0.95,
    "cagra": 0.7,
}

_FAST = dict(chunk_rows=128, gate_queries=16, max_side_rows=16)


def _service(x, kind="brute_force", **kw):
    svc = serve.SearchService(k=10, max_batch=4, max_delay_ms=0.5,
                              compaction=False, **kw)
    svc.add_index(kind, _build(kind, x), warmup=True)
    return svc


def test_policy_from_env(monkeypatch):
    monkeypatch.setenv("RAFT_TPU_COMPACT_MAX_SIDE_ROWS", "77")
    monkeypatch.setenv("RAFT_TPU_COMPACT_MAX_TOMBSTONE_FRAC", "0.5")
    monkeypatch.setenv("RAFT_TPU_COMPACT_INTERVAL_S", "0.25")
    monkeypatch.setenv("RAFT_TPU_COMPACT_HEADROOM_FRAC", "3.5")
    pol = CompactionPolicy.from_env()
    assert pol.max_side_rows == 77
    assert pol.max_tombstone_frac == 0.5
    assert pol.interval_s == 0.25
    assert pol.headroom_frac == 3.5
    assert not CompactionPolicy.disabled_by_env()
    monkeypatch.setenv("RAFT_TPU_COMPACT_DISABLED", "1")
    assert CompactionPolicy.disabled_by_env()


@pytest.mark.parametrize(
    "kind", ["brute_force", "ivf_flat", "ivf_pq", "ivf_pq_refined", "cagra"]
)
def test_compact_folds_mutations(kind, corpus):
    """One pass folds tombstones + side rows into the main structure,
    preserves every live id, and keeps shapes stable on the next pass."""
    x, q = corpus
    rng = np.random.default_rng(3)
    svc = _service(x, kind)
    try:
        mi = svc.get(kind)
        dead = rng.choice(N, size=60, replace=False)
        mi.delete(dead)
        new_rows = rng.standard_normal((40, D)).astype(np.float32)
        new_ids = np.asarray(mi.upsert(new_rows))

        keep = np.setdiff1d(np.arange(N), dead)
        live_ids = np.concatenate([keep, new_ids])
        live_rows = np.concatenate([x[keep], new_rows])
        _d, gt_rows = brute_force.knn(live_rows, q, 10)
        gt = live_ids[np.asarray(gt_rows)]

        comp = Compactor(svc, CompactionPolicy(**_FAST), start=False)
        res = comp.trigger_now(kind)
        assert res["status"] == "promoted", res
        assert res["folded_deletes"] == 60
        assert res["folded_side_rows"] == 40
        assert res["projected_peak_bytes"] <= res["budget_bytes"]

        served = svc.get(kind)
        assert served is not mi
        assert served.pending_mutations() == (0, 0)
        _d, ids = served.search(q, 10)
        rec = recall_at_k(np.asarray(ids), gt)
        assert rec >= _RECALL_FLOOR[kind], (kind, rec)
        if kind == "ivf_pq_refined":
            # the refine rows followed the renumbering: served distances
            # are still exact against the raw live rows
            assert served.refine_dataset.shape == (served.main_size, D)
            row = {int(g): r for g, r in zip(live_ids, live_rows)}
            i0 = np.asarray(ids)[0]
            np.testing.assert_allclose(
                np.asarray(_d)[0],
                [((row[int(g)] - q[0]) ** 2).sum() for g in i0], rtol=1e-4,
            )
            # a sharded rebuild would drop the refine leg: refused loudly
            out = comp.rebuild_sharded(kind)
            assert (out["status"], out["reason"]) == ("aborted", "refined")

        # ids survived the fold: writes through the retired handle land
        probe = int(keep[0])
        assert served.contains(probe)
        mi.delete([probe])
        assert not served.contains(probe)

        # second pass: same padded main shape (executables key on shapes)
        size1 = served.main_size
        res2 = comp.trigger_now(kind)
        assert res2["status"] == "promoted", res2
        assert svc.get(kind).main_size == size1
        assert not svc.get(kind).contains(probe)
        comp.stop()
    finally:
        svc.stop()


def test_gate_abort_rearms_and_degrades_healthz(corpus):
    x, q = corpus
    svc = _service(x)
    try:
        mi = svc.get("brute_force")
        mi.delete(np.arange(50))
        # an impossible slack: the shadow would have to beat serving by a
        # full point of recall, so the gate must refuse the promotion
        bad = Compactor(
            svc, CompactionPolicy(recall_slack=-1.1, **_FAST), start=False
        )
        svc.compactor = bad
        res = bad.trigger_now("brute_force")
        assert res["status"] == "aborted" and res["reason"] == "gate", res
        assert svc.get("brute_force") is mi          # serving untouched
        assert mi.pending_mutations()[0] == 50

        report = svc.healthz()
        check = report["indexes"]["brute_force"]["checks"]["compaction"]
        assert check["status"] == "DEGRADED", check
        assert "gate" in check["detail"]

        # cooldown re-arms the automatic loop: scan() skips the index
        bad.scan()
        assert svc.get("brute_force") is mi

        # a sane policy promotes and clears the abort
        good = Compactor(svc, CompactionPolicy(**_FAST), start=False)
        svc.compactor = good
        assert good.trigger_now("brute_force")["status"] == "promoted"
        report = svc.healthz()
        check = report["indexes"]["brute_force"]["checks"]["compaction"]
        assert check["status"] == "OK", check
        bad.stop()
        good.stop()
    finally:
        svc.stop()


def test_memory_budget_aborts_before_allocating(corpus):
    x, _q = corpus
    svc = _service(x)
    try:
        svc.get("brute_force").delete(np.arange(50))
        comp = Compactor(
            svc, CompactionPolicy(headroom_frac=1e-6, **_FAST), start=False
        )
        res = comp.trigger_now("brute_force")
        assert res["status"] == "aborted" and res["reason"] == "budget", res
        prom = svc.prometheus()
        assert "raft_tpu_compaction_peak_bytes" in prom
        assert "raft_tpu_compaction_aborts_total" in prom
        comp.stop()
    finally:
        svc.stop()


def test_pause_drain_trigger_now(corpus):
    x, _q = corpus
    svc = _service(x)
    try:
        mi = svc.get("brute_force")
        mi.upsert(np.random.default_rng(5).standard_normal(
            (32, D)).astype(np.float32))        # 32 >= max_side_rows=16
        comp = Compactor(svc, CompactionPolicy(**_FAST), start=False)
        svc.compactor = comp
        svc.pause_compaction()
        comp.scan()                              # paused: no trigger
        assert svc.get("brute_force") is mi
        assert svc.drain_compaction(timeout=1.0)
        svc.resume_compaction()
        comp.scan()                              # threshold crossed
        assert svc.get("brute_force") is not mi
        assert svc.drain_compaction(timeout=5.0)
        comp.stop()
    finally:
        svc.stop()


def test_service_owns_compactor_lifecycle(corpus, monkeypatch):
    x, _q = corpus
    svc = serve.SearchService(
        k=10, max_batch=4, compaction=CompactionPolicy(
            interval_s=0.05, **_FAST
        ),
    )
    svc.add_index("own", _build("brute_force", x), warmup=False)
    assert svc.compactor is not None
    assert svc.compactor.snapshot()["worker_alive"]
    svc.stop()
    assert not svc.compactor.snapshot()["worker_alive"]

    # env kill-switch: compaction=True builds the compactor but the
    # worker stays down
    monkeypatch.setenv("RAFT_TPU_COMPACT_DISABLED", "1")
    svc2 = serve.SearchService(k=10, compaction=True)
    assert svc2.compactor is not None
    assert not svc2.compactor.snapshot()["worker_alive"]
    svc2.stop()

    # no compactor: the control surface degrades gracefully
    svc3 = serve.SearchService(k=10)
    assert svc3.compactor is None
    with pytest.raises(RuntimeError):
        svc3.compact_now("nothing")
    assert svc3.drain_compaction(timeout=0.1)
    svc3.stop()


def test_mutation_pressure_gauges_in_prometheus(corpus):
    """Satellite: pending deletes / side rows / tombstone fraction are
    scrapeable per index, and retire with the index."""
    x, _q = corpus
    svc = _service(x)
    try:
        mi = svc.get("brute_force")
        mi.delete(np.arange(30))
        mi.upsert(np.random.default_rng(9).standard_normal(
            (12, D)).astype(np.float32))
        prom = svc.prometheus()
        assert (
            'raft_tpu_index_pending_deletes{index="brute_force"} 30' in prom
        ), prom
        assert 'raft_tpu_index_side_rows{index="brute_force"} 12' in prom
        assert 'raft_tpu_index_tombstone_frac{index="brute_force"}' in prom
        svc.remove_index("brute_force")
        prom = svc.prometheus()
        assert "raft_tpu_index_pending_deletes" not in prom or (
            'index="brute_force"' not in prom.split(
                "raft_tpu_index_pending_deletes"
            )[1].split("\n")[0]
        )
    finally:
        svc.stop()


def test_save_load_preserves_generation_and_id_map(tmp_path, corpus):
    """Satellite regression: a restored index must not reset its
    generation (executable-cache keys), its id sequence, or — after a
    compaction — its row→global-id map and structural-padding count."""
    x, q = corpus
    rng = np.random.default_rng(13)
    svc = _service(x)
    try:
        mi = svc.get("brute_force")
        mi.delete(rng.choice(N, size=40, replace=False))
        mi.upsert(rng.standard_normal((20, D)).astype(np.float32))
        comp = Compactor(svc, CompactionPolicy(**_FAST), start=False)
        assert comp.trigger_now("brute_force")["status"] == "promoted"
        served = svc.get("brute_force")
        # post-compaction churn so the snapshot carries every state kind
        served.delete([int(served._main_ids[0])])
        extra = served.upsert(rng.standard_normal((3, D)).astype(np.float32))

        path = str(tmp_path / "compacted.mut")
        served.save(path)
        back = serve.MutableIndex.load(path)

        assert back.generation == served.generation
        assert back._next_id == served._next_id
        assert back._n_structural == served._n_structural
        assert np.array_equal(back._main_ids, served._main_ids)
        assert back.pending_mutations() == served.pending_mutations()
        for i in extra:
            assert back.contains(int(i))
        d0, i0 = served.search(q, 10)
        d1, i1 = back.search(q, 10)
        assert np.array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(
            np.asarray(d0), np.asarray(d1), rtol=1e-5, atol=1e-5
        )
        comp.stop()
    finally:
        svc.stop()


def test_churn_soak_stays_bounded_with_zero_recompiles(corpus):
    """Satellite + acceptance: >= 20 upsert/delete/search cycles with the
    compactor enabled keep side rows and live bytes bounded, answer
    concurrent readers across every hot-swap without an error, and record
    zero post-warmup hot-path recompiles."""
    x, q = corpus
    rng = np.random.default_rng(21)
    pol = CompactionPolicy(
        max_side_rows=24, max_tombstone_frac=0.25, interval_s=0.05,
        chunk_rows=256, gate_queries=16,
    )
    svc = serve.SearchService(k=10, max_batch=16, max_delay_ms=0.5,
                              compaction=pol)
    try:
        svc.add_index("soak", _build("brute_force", x), warmup=True)
        comp = svc.compactor
        live = set(range(N))

        def churn(n_up, n_del):
            mi = svc.get("soak")
            rows = rng.standard_normal((n_up, D)).astype(np.float32)
            ids = [int(i) for i in mi.upsert(rows)]
            # delete only OLDER rows, so this cycle's upserts stay live
            # for the visibility assertion below
            pool = sorted(live)
            dead = rng.choice(pool, size=n_del, replace=False)
            mi.delete(dead)
            live.difference_update(int(i) for i in dead)
            live.update(ids)
            return rows, ids

        # warm phase: first churn + first compaction establish the
        # pow2-padded shapes and warm every post-swap variant; hot-path
        # attribution starts clean after it, like any warmup
        churn(16, 16)
        assert svc.compact_now("soak")["status"] == "promoted"
        svc.search("soak", q)
        svc._batcher("soak").metrics.reset_hot_path()

        errors = []
        stop_reading = threading.Event()

        def reader():
            while not stop_reading.is_set():
                try:
                    _d, ids = svc.search("soak", q[:3])
                    if ids.shape != (3, 10):
                        errors.append(f"bad shape {ids.shape}")
                except Exception as exc:  # noqa: BLE001
                    errors.append(repr(exc))

        t = threading.Thread(target=reader, daemon=True)
        t.start()

        try:
            max_side = 0
            max_bytes = 0
            base_bytes = svc.get("soak").device_bytes()
            for cycle in range(22):
                rows, ids = churn(16, 16)
                _d, got = svc.search("soak", rows[:4])
                got = np.asarray(got)
                for j in range(4):
                    assert ids[j] in got[j], (cycle, ids[j], got[j])
                comp.scan()  # deterministic trigger (worker also runs)
                deletes, side = svc.get("soak").pending_mutations()
                max_side = max(max_side, side)
                max_bytes = max(max_bytes, svc.get("soak").device_bytes())
        finally:
            stop_reading.set()
            t.join(timeout=10)
        assert svc.drain_compaction(timeout=30)

        assert not errors, errors[:5]
        assert comp.snapshot()["compactions"] >= 3
        # bounded: side rows never past one trigger's worth of backlog,
        # live bytes flat at the first compacted footprint
        assert max_side <= 2 * pol.max_side_rows, max_side
        assert max_bytes <= 1.5 * base_bytes, (max_bytes, base_bytes)
        st = svc.stats("soak")
        assert st["recompiles"] == 0, (
            f"hot path recompiled {st['recompiles']}x during the soak"
        )
        # the survivors answer: every live id, none of the dead
        mi = svc.get("soak")
        sample = rng.choice(sorted(live), size=20, replace=False)
        for i in sample:
            assert mi.contains(int(i))
    finally:
        svc.stop()
