"""Run registry + regression ledger (ISSUE 16), jax-free units: atomic
append under torn-write injection, the tolerant metric extraction the
BENCH_r* backfill depends on (post-PR-15 keys absent → metric absent,
never KeyError), the one-shot idempotent backfill over fixture captures
of the shapes the driver has written, trailing median+MAD trend verdicts
(regression vs jitter), and the ``trend``/``compare`` CLI — including a
poisoned-jax subprocess proving ``obs trend`` never imports jax."""

import json
import os
import subprocess
import sys

import pytest

from tpuflow.obs import registry as reg
from tpuflow.obs.__main__ import main as obs_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HEADLINE = {"metric": "sharded_ckpt_save_restore_throughput",
             "unit": "GB/s"}


@pytest.fixture
def bench_dir(tmp_path):
    """Five driver captures, one of each shape the importer has met: a
    bare headline (r01-r03, before the compact digest existed), a null
    ``parsed`` (r04: the record outgrew the captured tail), and a digest
    with the train summary (r05). Values are made up."""
    d = tmp_path / "captures"
    d.mkdir()
    parsed = {
        1: {**_HEADLINE, "value": 1.5, "vs_baseline": 0.75},
        2: {**_HEADLINE, "value": 2.5, "vs_baseline": 1.25},
        3: {**_HEADLINE, "value": 3.0, "vs_baseline": 1.5},
        4: None,
        5: {**_HEADLINE, "value": 3.5, "vs_baseline": 1.75, "summary": {
            "host_combined_gbps": 3.5, "disk_combined_gbps": 0.5,
            "train": {"platform": "tpu", "mfu": 0.5,
                      "tokens_per_s": 1000.0},
            "spec_decode": {"numerics_ok": False, "speedup": None},
            "git": "abc1234",
        }},
    }
    for n, p in parsed.items():
        (d / f"BENCH_r0{n}.json").write_text(json.dumps(
            {"n": n, "cmd": "python bench.py", "rc": 0, "tail": "",
             "parsed": p}
        ))
    return str(d)


def _mk(run_id, metrics, ts=0.0):
    return reg.make_record(
        "bench", metrics, source="test", run_id=run_id, ts=ts
    )


# ------------------------------------------------------------- appends
def test_append_read_roundtrip_and_event(tmp_path):
    from tpuflow import obs

    path = str(tmp_path / "reg.jsonl")
    obs.configure(str(tmp_path / "obs"), proc=0)
    try:
        assert reg.append_record(path, _mk("a", {"mfu": 0.4}))
        assert reg.append_record(path, _mk("b", {"mfu": 0.41}))
        obs.flush()
    finally:
        obs.configure(None)
    recs = reg.read_registry(path)
    assert [r["run_id"] for r in recs] == ["a", "b"]
    assert recs[0]["schema"] == reg.SCHEMA
    # The append leaves its audit event in the stream.
    events = []
    d = str(tmp_path / "obs")
    for name in os.listdir(d):
        if name.startswith("events."):
            events.extend(obs.read_events(os.path.join(d, name)))
    appends = [e for e in events if e["name"] == "registry.append"]
    assert len(appends) == 2
    assert appends[0]["kind"] == "event"
    assert appends[0]["run_id"] == "a"


def test_reader_skips_torn_and_corrupt_lines(tmp_path):
    """Crash-safety contract: a torn final line (no newline — the
    append died mid-write), a corrupt interior line, and a non-record
    JSON value are all skipped; the valid records survive."""
    path = str(tmp_path / "reg.jsonl")
    assert reg.append_record(path, _mk("a", {"mfu": 0.4}))
    with open(path, "a") as f:
        f.write('{"not": "a record"}\n')  # no metrics dict
        f.write("{garbage}\n")  # corrupt but newline-terminated
    assert reg.append_record(path, _mk("b", {"mfu": 0.41}))
    with open(path, "a") as f:
        f.write('{"schema": 1, "run_id": "torn", "metrics": {"m"')
    recs = reg.read_registry(path)
    assert [r["run_id"] for r in recs] == ["a", "b"]
    # A later append after the torn line starts ON the torn line —
    # that is the crashed writer's incomplete record merged into the
    # next one; both are then skipped but every prior and later
    # complete line still reads. (O_APPEND writes are whole-line, so
    # this only happens when a previous process died mid-write.)
    assert reg.append_record(path, _mk("c", {"mfu": 0.42}))
    assert reg.append_record(path, _mk("d", {"mfu": 0.43}))
    recs = reg.read_registry(path)
    assert [r["run_id"] for r in recs] == ["a", "b", "d"]
    assert reg.read_registry(str(tmp_path / "missing.jsonl")) == []


# -------------------------------------------- tolerant extraction
def test_digest_metrics_tolerates_missing_post_pr15_keys():
    """The satellite bugfix pinned: digests predating the PR 15 keys
    (hbm_peak_frac, programs_ledger, fleet snapshots) degrade to
    'metric absent' — never KeyError."""
    legacy = {
        "host_combined_gbps": 1.76,
        "train": {"platform": "cpu", "tokens_per_s": 6929.4, "mfu": None},
    }
    m = reg.digest_metrics(legacy)
    assert m["host_combined_gbps"] == 1.76
    assert m["train_tokens_per_s"] == 6929.4
    assert "train_mfu" not in m  # null leaf -> absent
    assert "hbm_peak_frac" not in m
    assert "paged_vs_slot" not in m
    rich = {
        "serving": {"hbm_peak_frac": 0.63, "ttft_p99_s": 0.12},
        "serving_paged": {"vs_slot": 1.31},
        "spec_decode": {"numerics_ok": False, "speedup": None},
    }
    m = reg.digest_metrics(rich)
    assert m["hbm_peak_frac"] == 0.63
    assert m["paged_vs_slot"] == 1.31
    assert m["spec_decode_numerics_ok"] == 0.0  # bool -> 0/1
    assert "spec_decode_speedup" not in m
    assert reg.digest_metrics(None) == {}
    assert reg.bench_metrics({"value": "NaN-ish"}) == ({}, {})


def test_bench_metrics_all_generations():
    # r01 shape: bare metric/value.
    m, prov = reg.bench_metrics(
        {"metric": "x", "value": 1.7614, "unit": "GB/s",
         "vs_baseline": 0.8807}
    )
    assert m == {"host_combined_gbps": 1.7614, "vs_baseline": 0.8807}
    assert prov == {}
    # r02/r03 shape: full record with extra.train.
    m, prov = reg.bench_metrics(
        {"value": 3.93, "extra": {
            "tiers": {"disk": {"combined_gbps": 0.46}},
            "train": {"platform": "cpu", "tokens_per_s": 6929.4,
                      "mfu": None},
        }}
    )
    assert m["disk_combined_gbps"] == 0.46
    assert m["train_tokens_per_s"] == 6929.4
    assert prov["platform"] == "cpu"
    # r05 shape: compact summary digest.
    m, prov = reg.bench_metrics(
        {"value": 3.89, "summary": {
            "host_combined_gbps": 3.89,
            "train": {"platform": "tpu", "mfu": 0.4277,
                      "tokens_per_s": 113207.9},
            "git": "11c8ff0",
        }}
    )
    assert m["train_mfu"] == 0.4277
    assert prov == {"platform": "tpu", "git": "11c8ff0"}


# ------------------------------------------------------------ backfill
def test_backfill_bench_history_idempotent(tmp_path, bench_dir):
    """The one-shot importer over driver captures: every round imports
    (r04's null parsed included), legacy rounds simply carry fewer
    metrics, and a second run imports nothing."""
    path = str(tmp_path / "reg.jsonl")
    assert reg.backfill_bench(bench_dir, path) == 5
    assert reg.backfill_bench(bench_dir, path) == 0  # idempotent
    recs = {r["run_id"]: r for r in reg.read_registry(path)}
    r01 = recs["BENCH_r01"]
    assert r01["metrics"]["host_combined_gbps"] == pytest.approx(1.5)
    assert "hbm_peak_frac" not in r01["metrics"]  # absent, not KeyError
    r05 = recs["BENCH_r05"]
    assert r05["metrics"]["train_mfu"] == pytest.approx(0.5)
    assert r05["metrics"]["spec_decode_numerics_ok"] == 0.0
    assert r05.get("platform") == "tpu"
    assert r05.get("git") == "abc1234"
    assert "BENCH_r04" in recs  # null parsed still imports


# ---------------------------------------------------------- trend math
def test_trend_jitter_is_ok_regression_is_flagged():
    history = [
        _mk(f"r{i}", {"train_mfu": 0.42 + 0.002 * (i % 3),
                      "serve_ttft_p99_s": 0.100 + 0.001 * (i % 2)},
            ts=float(i))
        for i in range(5)
    ]
    # In-family jitter: ok on both metrics.
    rows = {r["metric"]: r for r in reg.verdict_rows(
        history, {"train_mfu": 0.421, "serve_ttft_p99_s": 0.1005},
        window=5, zmads=8.0,
    )}
    assert rows["train_mfu"]["verdict"] == "ok"
    assert rows["serve_ttft_p99_s"]["verdict"] == "ok"
    # A real cliff: mfu collapse REGRESSED; ttft collapse (lower is
    # better) improved; a brand-new metric is "new"; a metric the
    # current run dropped is "absent".
    rows = {r["metric"]: r for r in reg.verdict_rows(
        history, {"train_mfu": 0.20, "paged_vs_slot": 1.3},
        window=5, zmads=8.0,
    )}
    assert rows["train_mfu"]["verdict"] == "REGRESSED"
    assert rows["train_mfu"]["n"] == 5
    assert rows["paged_vs_slot"]["verdict"] == "new"
    assert rows["serve_ttft_p99_s"]["verdict"] == "absent"
    rows = {r["metric"]: r for r in reg.verdict_rows(
        history, {"serve_ttft_p99_s": 0.02}, window=5, zmads=8.0,
    )}
    assert rows["serve_ttft_p99_s"]["verdict"] == "improved"


def test_trend_constant_history_has_jitter_floor():
    """MAD 0 (identical history) must not make a 0.5% wiggle
    infinitely significant: the 1% floor keeps it 'ok'."""
    history = [_mk(f"r{i}", {"m": 100.0}, ts=float(i)) for i in range(5)]
    rows = reg.verdict_rows(history, {"m": 100.4}, window=5, zmads=8.0)
    assert rows[0]["verdict"] == "ok"
    rows = reg.verdict_rows(history, {"m": 50.0}, window=5, zmads=8.0)
    assert rows[0]["verdict"] == "REGRESSED"


def test_compare_rows_direction_and_absent():
    a = _mk("a", {"train_mfu": 0.40, "serve_ttft_p99_s": 0.10,
                  "host_combined_gbps": 3.9})
    b = _mk("b", {"train_mfu": 0.44, "serve_ttft_p99_s": 0.20,
                  "hbm_peak_frac": 0.6})
    rows = {r["metric"]: r for r in reg.compare_rows(a, b)}
    assert rows["train_mfu"]["verdict"] == "improved"
    assert rows["train_mfu"]["delta"] == pytest.approx(0.04)
    assert rows["serve_ttft_p99_s"]["verdict"] == "REGRESSED"
    assert rows["host_combined_gbps"]["verdict"] == "absent"
    assert rows["hbm_peak_frac"]["verdict"] == "absent"


# ------------------------------------------------------------------ CLI
@pytest.fixture
def backfilled(tmp_path, monkeypatch, bench_dir):
    path = str(tmp_path / "reg.jsonl")
    assert reg.backfill_bench(bench_dir, path) == 5
    monkeypatch.setenv("TPUFLOW_REGISTRY_PATH", path)
    return path


def test_trend_cli_over_backfilled_history(backfilled, capsys):
    assert obs_main(["trend"]) == 0
    out = capsys.readouterr().out
    assert "metric" in out and "verdict" in out
    assert "host_combined_gbps" in out
    # --metric= filters; --json dumps rows.
    assert obs_main(["trend", "--metric=train_mfu", "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert [r["metric"] for r in rows] == ["train_mfu"]


def test_compare_cli_and_prefix_match(backfilled, capsys):
    assert obs_main(["compare", "BENCH_r01", "BENCH_r05"]) == 0
    out = capsys.readouterr().out
    assert "host_combined_gbps" in out and "verdict" in out
    # r01 lacks every post-PR-15 metric: absent rows, no KeyError.
    assert "absent" in out
    assert obs_main(["compare", "BENCH_r01", "nope"]) == 1
    assert "nope" in capsys.readouterr().err


def test_backfill_cli(tmp_path, monkeypatch, capsys, bench_dir):
    path = str(tmp_path / "reg.jsonl")
    monkeypatch.setenv("TPUFLOW_REGISTRY_PATH", path)
    assert obs_main(["registry-backfill", bench_dir]) == 0
    assert "imported" in capsys.readouterr().out
    assert len(reg.read_registry(path)) == 5


def test_trend_cli_empty_registry(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv(
        "TPUFLOW_REGISTRY_PATH", str(tmp_path / "empty.jsonl")
    )
    assert obs_main(["trend"]) == 1
    assert "registry" in capsys.readouterr().err


def test_trend_cli_is_jax_free(backfilled):
    """The acceptance clause: obs trend renders the per-metric table
    with jax poisoned out of the interpreter entirely."""
    code = (
        "import sys; sys.modules['jax'] = None; "
        "from tpuflow.obs.__main__ import main; "
        "sys.exit(main(['trend']))"
    )
    env = dict(os.environ, TPUFLOW_REGISTRY_PATH=backfilled)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "host_combined_gbps" in proc.stdout


# ----------------------------------------------------- live run appends
def test_maybe_append_live_knob_gated(tmp_path, monkeypatch):
    monkeypatch.delenv("TPUFLOW_REGISTRY_PATH", raising=False)
    assert reg.maybe_append_live("train", {"goodput_fraction": 0.9}) is False
    path = str(tmp_path / "reg.jsonl")
    monkeypatch.setenv("TPUFLOW_REGISTRY_PATH", path)
    snap = {
        "goodput_fraction": 0.93, "tokens_per_s": 1000.0,
        "steps": 10, "serve_ttft_p95_s": 0.05,
    }
    assert reg.maybe_append_live("train", snap) is True
    (rec,) = reg.read_registry(path)
    assert rec["kind"] == "train"
    assert rec["metrics"]["goodput_fraction"] == 0.93
    assert rec["metrics"]["serve_ttft_p95_s"] == 0.05


def test_snapshot_metrics_prefers_mergeable_buckets():
    """TTFT/ITL percentiles come from the mergeable histogram buckets
    when the snapshot carries them — the same source the fleet merges —
    not the pre-aggregated gauges."""
    from tpuflow.obs.fleet import MergeableHistogram, hist_percentiles

    h = MergeableHistogram()
    for v in (0.01, 0.02, 0.03, 0.2):
        h.observe(v)
    snap = {
        "serve_ttft_hist": h.to_dict(),
        "serve_ttft_p99_s": 123.0,  # stale gauge: must lose
        "serve_itl_p99_s": 0.007,  # no itl hist: gauge fallback
        "goodput_fraction": 0.5,
    }
    m = reg.snapshot_metrics(snap)
    exact = hist_percentiles(h.to_dict())
    assert m["serve_ttft_p99_s"] == exact["p99"]
    assert m["serve_itl_p99_s"] == 0.007
