"""Bench record round-trip + the ``compare`` regression gate: identical
records pass (exit 0), a synthetic 2x latency regression fails (exit
nonzero), historical BENCH_r0N.json driver wrappers load, and the CLI
(``python -m raft_tpu.bench compare``) agrees with the library."""

import json
import os
import subprocess
import sys

import pytest

from raft_tpu.bench import export

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PAYLOAD = {
    "metric": "serve_qps_test_n8k_k10",
    "value": 1000.0,
    "unit": "queries/s",
    "platform": "cpu",
    "p50_ms": 2.0,
    "p99_ms": 5.0,
    "recall": 0.97,
    "recompiles": 0,
}


def _write(tmp_path, name, payload):
    path = str(tmp_path / name)
    export.write_bench_record(payload, path)
    return path


# ---------------------------------------------------------------------------
# record envelope


def test_record_round_trip(tmp_path):
    path = _write(tmp_path, "r.json", PAYLOAD)
    doc = json.load(open(path))
    assert doc["schema"] == "raft_tpu.bench"
    assert doc["schema_version"] == export.BENCH_SCHEMA_VERSION
    loaded = export.load_record(path)
    # written records carry the kernel-path attribution stamp; a payload
    # that didn't set one gets the env-derived default
    assert loaded.pop("kernel_path") == {"pallas": False}
    assert loaded == PAYLOAD


def test_kernel_path_stamp_and_passthrough(monkeypatch):
    stamped = export.bench_record(PAYLOAD)["record"]
    assert stamped["kernel_path"] == {"pallas": False}
    monkeypatch.setenv("RAFT_TPU_PALLAS", "1")
    assert export.kernel_path() == {"pallas": True}
    # a leg that measured its own routing wins over the env default
    explicit = export.bench_record(
        dict(PAYLOAD, kernel_path={"pallas": False})
    )["record"]
    assert explicit["kernel_path"] == {"pallas": False}
    # metric/dtype form asks the shared pallas_scan_enabled gate
    import jax.numpy as jnp

    assert export.kernel_path("sqeuclidean", jnp.float32)["pallas"] is True
    monkeypatch.delenv("RAFT_TPU_PALLAS")
    assert export.kernel_path("sqeuclidean", jnp.float32)["pallas"] is False


def test_kernel_path_change_is_informational_not_regression():
    base = dict(PAYLOAD, kernel_path={"pallas": False})
    cand = dict(PAYLOAD, kernel_path={"pallas": True})
    ok, lines = export.compare_records(base, cand)
    assert ok, lines
    assert any("kernel_path" in ln and "info" in ln for ln in lines)
    # old records without the field stay silent
    ok, lines = export.compare_records(PAYLOAD, PAYLOAD)
    assert ok and not any("kernel_path" in ln for ln in lines)


def test_load_bare_payload(tmp_path):
    path = str(tmp_path / "bare.json")
    json.dump(PAYLOAD, open(path, "w"))
    assert export.load_record(path) == PAYLOAD


def test_load_driver_wrapper(tmp_path):
    path = str(tmp_path / "BENCH_r99.json")
    json.dump({"n": 99, "cmd": "python3 benchmark/run.py", "rc": 0,
               "tail": "...", "parsed": PAYLOAD}, open(path, "w"))
    assert export.load_record(path) == PAYLOAD


def test_load_rejects_unknown_schema_version(tmp_path):
    path = str(tmp_path / "future.json")
    doc = export.bench_record(PAYLOAD)
    doc["schema_version"] = export.BENCH_SCHEMA_VERSION + 1
    json.dump(doc, open(path, "w"))
    with pytest.raises(ValueError, match="schema_version"):
        export.load_record(path)


def test_load_rejects_payload_without_metric(tmp_path):
    path = str(tmp_path / "junk.json")
    json.dump({"value": 1.0}, open(path, "w"))
    with pytest.raises(ValueError, match="metric"):
        export.load_record(path)


def test_bench_record_rejects_non_payload():
    with pytest.raises(ValueError):
        export.bench_record({"value": 1.0})


def test_write_suppressed_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv(export.RECORD_PATH_ENV, "-")
    assert export.write_bench_record(PAYLOAD) == ""


def test_historical_bench_records_still_load():
    """The driver's BENCH_r0N.json artifacts are the baselines CI points
    at — every one in the repo must stay loadable and self-comparable."""
    records = sorted(
        f for f in os.listdir(REPO)
        if f.startswith("BENCH_r") and f.endswith(".json")
    )
    assert records, "no historical bench records found"
    loaded = 0
    for name in records:
        path = os.path.join(REPO, name)
        if json.load(open(path)).get("parsed") is None:
            continue  # that round's bench emitted no line (rc!=0)
        payload = export.load_record(path)
        assert "metric" in payload
        ok, lines = export.compare_records(payload, payload)
        assert ok, (name, lines)
        loaded += 1
    assert loaded >= 1


# ---------------------------------------------------------------------------
# comparison semantics


def test_identical_records_pass():
    ok, lines = export.compare_records(PAYLOAD, PAYLOAD)
    assert ok and lines[-1] == "PASS"


def test_2x_latency_regression_fails():
    worse = dict(PAYLOAD, p99_ms=10.0, p50_ms=4.0)
    ok, lines = export.compare_records(PAYLOAD, worse)
    assert not ok
    assert any("p99_ms" in ln and "REGRESSION" in ln for ln in lines)


def test_2x_throughput_drop_fails_and_gain_passes():
    ok, _ = export.compare_records(PAYLOAD, dict(PAYLOAD, value=500.0))
    assert not ok
    ok, _ = export.compare_records(PAYLOAD, dict(PAYLOAD, value=2000.0))
    assert ok


def test_latency_unit_direction_is_lower_is_better():
    lat = {"metric": "m", "value": 10.0, "unit": "ms", "platform": "cpu"}
    ok, _ = export.compare_records(lat, dict(lat, value=20.0))
    assert not ok
    ok, _ = export.compare_records(lat, dict(lat, value=5.0))
    assert ok


def test_noise_within_rtol_passes():
    ok, _ = export.compare_records(PAYLOAD, dict(PAYLOAD, value=900.0))
    assert ok  # -10% < 25% tolerance: noise, not regression
    ok, _ = export.compare_records(
        PAYLOAD, dict(PAYLOAD, value=900.0), rtol=0.05
    )
    assert not ok  # caller may tighten


def test_recall_absolute_tolerance():
    ok, _ = export.compare_records(PAYLOAD, dict(PAYLOAD, recall=0.96))
    assert ok
    ok, lines = export.compare_records(PAYLOAD, dict(PAYLOAD, recall=0.90))
    assert not ok
    assert any("recall" in ln and "REGRESSION" in ln for ln in lines)


def test_hot_path_recompiles_are_zero_tolerance():
    ok, lines = export.compare_records(PAYLOAD, dict(PAYLOAD, recompiles=3))
    assert not ok
    assert any("recompiles" in ln for ln in lines)


def test_mismatched_metric_or_platform_skips():
    ok, lines = export.compare_records(
        PAYLOAD, dict(PAYLOAD, metric="other_metric")
    )
    assert ok and lines[0].startswith("SKIP")
    ok, lines = export.compare_records(
        PAYLOAD, dict(PAYLOAD, platform="tpu")
    )
    assert ok and lines[0].startswith("SKIP")


# ---------------------------------------------------------------------------
# CLI exit codes


def _run_cli(cmd, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=120
    )


@pytest.mark.parametrize(
    "entry",
    [[sys.executable, "-m", "raft_tpu.bench", "compare"]],
    ids=["raft_tpu.bench"],
)
def test_cli_exit_codes(entry, tmp_path):
    base = _write(tmp_path, "base.json", PAYLOAD)
    same = _write(tmp_path, "same.json", PAYLOAD)
    worse = _write(
        tmp_path, "worse.json", dict(PAYLOAD, value=480.0, p99_ms=11.0)
    )
    ok = _run_cli(entry + ["--baseline", base, "--candidate", same])
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert "PASS" in ok.stdout

    bad = _run_cli(entry + ["--baseline", base, "--candidate", worse])
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "REGRESSION" in bad.stdout

    missing = _run_cli(entry + ["--baseline", str(tmp_path / "nope.json"),
                                "--candidate", same])
    assert missing.returncode == 2
