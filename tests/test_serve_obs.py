"""Serving observatory (ISSUE 13), host-pure layer: the engine-time
ledger's bucket/cursor math, SLO resolution and counting, the access-log
round trip, and the serve-summary CLI reproducing the live /metrics
TTFT/ITL percentiles from the access log alone — all with zero compiles
(the engine-integration coverage lives in tests/test_serve.py)."""

import json
import os
import time

import pytest

from tpuflow.obs import serve_ledger as sl
from tpuflow.obs.export import prometheus_text
from tpuflow.obs.goodput import ProcessLedger


# --------------------------------------------------------------- ledger
def test_serve_ledger_buckets_sum_by_construction():
    """Every charged span lands in its bucket, every gap between
    charges lands in host_sched, and snapshot() settles the trailing
    tail — so the buckets sum to the measured wall EXACTLY (the
    acceptance criterion's 5% slack only covers report rounding)."""
    led = sl.ServeLedger()
    with led.bucket("prefill"):
        time.sleep(0.004)
    time.sleep(0.002)  # uncharged gap -> host_sched
    with led.bucket("decode"):
        time.sleep(0.006)
    with led.bucket("verify"):
        time.sleep(0.003)
    with led.bucket("insert"):
        time.sleep(0.001)
    with led.bucket("idle"):
        time.sleep(0.002)
    snap = led.snapshot()
    assert set(snap["buckets"]) == set(sl.SERVE_BUCKETS)
    assert sum(snap["buckets"].values()) == pytest.approx(
        snap["wall_s"], rel=1e-9
    )
    for b in ("prefill", "decode", "verify", "insert", "idle"):
        assert snap["buckets"][b] > 0
    assert snap["buckets"]["host_sched"] > 0
    assert sum(snap["fractions"].values()) == pytest.approx(1.0)
    # fractions() is the non-mutating live view: pending tail counted
    # as host_sched, sums to ~1 without settling the cursor.
    led2 = sl.ServeLedger()
    with led2.bucket("decode"):
        time.sleep(0.002)
    time.sleep(0.002)
    fr = led2.fractions()
    assert sum(fr.values()) == pytest.approx(1.0, abs=1e-3)
    assert fr["host_sched"] > 0
    # A snapshot after reset starts a fresh window.
    led.reset()
    assert sum(led.snapshot()["buckets"].values()) == pytest.approx(
        led.snapshot()["wall_s"], abs=1e-3
    )
    with pytest.raises(KeyError, match="bucket"):
        led.bucket("not_a_bucket")


def test_serve_ledger_efficiency_and_spec_economics():
    """Occupancy-weighted decode utilization, masked-row waste from the
    group partition, and speculative drafted-vs-accepted accounting."""
    led = sl.ServeLedger()
    assert led.decode_utilization is None
    assert led.masked_row_waste is None
    # Block 1: 8-row batch, 4 live in this group, 6 live engine-wide
    # (2 rows belong to another group: masked waste).
    led.note_decode_block(8, 4, 6)
    # Block 2: a verify block, 2 drafted tokens/row over 2 rows; 5
    # committed = 2 rows' bonus + 3 accepted drafts.
    led.note_decode_block(8, 2, 2, spec=True, drafted=4, committed=5)
    assert led.decode_utilization == pytest.approx(6 / 16)
    assert led.masked_row_waste == pytest.approx(2 / 16)
    assert led.spec_drafted == 4
    assert led.spec_accepted == 3
    assert led.spec_wasted == 1
    snap = led.snapshot()
    assert snap["decode_utilization"] == pytest.approx(6 / 16)
    assert snap["spec_wasted"] == 1


# One scripted block: (rows, pages, group_live). The engine of the
# script has 16 slots of 1,024 positions in pages of 16.
_READ_SCRIPTS = {
    "top-rung-only": [(16, 64, 3), (16, 64, 16)],
    "rows-and-width": [(4, 32, 3), (8, 64, 5), (16, 64, 12), (4, 64, 1)],
    "one-short-block": [(4, 32, 4)],
}


@pytest.mark.parametrize("script", sorted(_READ_SCRIPTS))
def test_decode_read_fraction_over_scripted_blocks(script):
    """``serve.decode_read_fraction`` is in the catalog and reads
    sum(R * W * page_size) / sum(max_slots * n_ctx) over the blocks,
    while ``decode_utilization`` still reads live rows over
    ``max_slots`` whatever rung a block ran at (the benchmark's
    ``decode_bw_share.serve`` multiplies it back by ``max_slots``)."""
    from tpuflow.obs.catalog import CATALOG

    assert CATALOG["serve.decode_read_fraction"][0] == "gauge"
    slots, n_ctx, page = 16, 1024, 16
    led = sl.ServeLedger()
    assert led.decode_read_fraction is None
    blocks = _READ_SCRIPTS[script]
    for rows, pages, live in blocks:
        led.note_decode_block(
            slots, live, live, read_positions=rows * pages * page,
            full_positions=slots * n_ctx,
        )
    want = sum(r * w * page for r, w, _ in blocks) / (
        len(blocks) * slots * n_ctx
    )
    assert led.decode_read_fraction == pytest.approx(want)
    assert (want == 1.0) == (script == "top-rung-only")
    assert led.decode_utilization == pytest.approx(
        sum(live for _, _, live in blocks) / (len(blocks) * slots)
    )
    snap = led.snapshot()
    assert snap["decode_read_fraction"] == pytest.approx(want)
    assert snap["decode_utilization"] == led.decode_utilization
    led.reset()
    assert led.decode_read_fraction is None


def test_serve_ledger_slo_checks_and_env_resolution(monkeypatch):
    led = sl.ServeLedger(slo_ttft_s=0.1, slo_itl_s=0.01)
    assert not led.check_ttft(0.05)
    assert led.check_ttft(0.2)
    assert not led.check_itl(0.005)
    assert led.check_itl(0.02)
    assert led.check_itl(None) is False
    assert led.slo_violations == 2
    assert led.slo_ttft_violations == 1 and led.slo_itl_violations == 1
    # Unarmed ledger never counts.
    off = sl.ServeLedger()
    assert not off.check_ttft(1e9) and off.slo_violations == 0
    # Knob resolution: ms -> s, malformed/non-positive/unset -> off.
    monkeypatch.setenv("TPUFLOW_SERVE_SLO_TTFT_MS", "250")
    assert sl.resolve_slo_s("TPUFLOW_SERVE_SLO_TTFT_MS") == pytest.approx(
        0.25
    )
    monkeypatch.setenv("TPUFLOW_SERVE_SLO_TTFT_MS", "banana")
    assert sl.resolve_slo_s("TPUFLOW_SERVE_SLO_TTFT_MS") is None
    monkeypatch.setenv("TPUFLOW_SERVE_SLO_TTFT_MS", "0")
    assert sl.resolve_slo_s("TPUFLOW_SERVE_SLO_TTFT_MS") is None
    monkeypatch.delenv("TPUFLOW_SERVE_SLO_TTFT_MS", raising=False)
    assert sl.resolve_slo_s("TPUFLOW_SERVE_SLO_TTFT_MS") is None
    # Concatenated so this file's own tree scan doesn't flag the fixture.
    with pytest.raises(KeyError, match="undeclared"):
        sl.resolve_slo_s("TPUFLOW_" + "SERVE_SLO_TYPO_MS")


def test_group_key():
    assert sl.group_key(False, False) == "fp.plain"
    assert sl.group_key(False, True) == "fp.spec"
    assert sl.group_key(True, False) == "int8.plain"
    assert sl.group_key(True, True) == "int8.spec"
    assert set(sl.GROUPS) == {
        sl.group_key(q, s) for q in (False, True) for s in (False, True)
    }


# ----------------------------------------------------------- access log
def _mk_record(i, group="fp.plain", ttft=0.01, itl=(0.002,), reason="budget",
               slo=0, tokens=5):
    return {
        "request": i,
        "ts": 100.0 + i,
        "group": group,
        "quant": group.startswith("int8"),
        "spec": group.endswith("spec"),
        "prompt_len": 4,
        "tokens": tokens,
        "terminal": "complete" if reason != "drained" else "drained",
        "finish_reason": reason,
        "ttft_s": ttft,
        "itl_s": list(itl),
        "slo_violations": slo,
    }


def test_access_log_roundtrip_and_summary(tmp_path):
    """AccessLog writes whole JSONL lines a mid-run reader can load;
    summarize_access splits percentiles by traffic group and folds
    finish reasons + SLO counts."""
    run_dir = str(tmp_path / "run")
    log = sl.AccessLog(os.path.join(run_dir, "obs"), proc=0)
    recs = [
        _mk_record(0, "fp.plain", ttft=0.01, itl=(0.002, 0.004)),
        _mk_record(1, "int8.spec", ttft=0.03, itl=(0.001,), slo=2),
        _mk_record(2, "fp.plain", ttft=0.02, reason="eos"),
        _mk_record(3, "fp.plain", ttft=None, itl=(), reason="drained"),
    ]
    for r in recs:
        log.write(r)
    # A torn tail (live writer) must not break the reader.
    with open(log.path, "a") as f:
        f.write('{"request": 99, "torn...')
    loaded = sl.load_access_log(run_dir)
    assert [r["request"] for r in loaded] == [0, 1, 2, 3]
    # Pointing straight at the obs dir works too (mid-run shells).
    assert len(sl.load_access_log(os.path.join(run_dir, "obs"))) == 4
    s = sl.summarize_access(loaded)
    assert s["requests"] == 4
    assert s["tokens"] == 20
    assert s["slo_violations"] == 2
    assert s["finish_reasons"] == {"budget": 2, "drained": 1, "eos": 1}
    assert s["ttft"]["count"] == 3  # the drained request never admitted
    assert s["itl"]["count"] == 4   # 2 + 1 + 1 ticks across the groups
    assert set(s["by_group"]) == {"fp.plain", "int8.spec"}
    assert s["by_group"]["int8.spec"]["ttft"]["p50"] == pytest.approx(0.03)
    # Empty log: summary is well-formed, reader returns [].
    assert sl.load_access_log(str(tmp_path / "nope")) == []
    empty = sl.summarize_access([])
    assert empty["requests"] == 0 and empty["ttft"] is None


def test_serve_summary_reproduces_metrics_percentiles():
    """The acceptance parity: the SAME TTFT/ITL observations fed to the
    live process ledger (what /metrics renders) and written as access
    records produce IDENTICAL p50/p95/p99 — both sides use
    serve_ledger.pctl, so serve-summary reproduces /metrics from the
    access log alone."""
    ttfts = [0.011, 0.035, 0.002, 0.090, 0.041, 0.017, 0.064, 0.008]
    itls = [0.0021, 0.0008, 0.0107, 0.0044, 0.0031, 0.0090, 0.0012]
    led = ProcessLedger()
    led.note_serve_state(queue_depth=0, live_slots=1, max_slots=2)
    for t in ttfts:
        led.note_serve_ttft(t)
    for v in itls:
        led.note_serve_itl(v)
    snap = led.snapshot()
    records = [
        _mk_record(i, ttft=t, itl=()) for i, t in enumerate(ttfts)
    ]
    records[0]["itl_s"] = list(itls)
    s = sl.summarize_access(records)
    for q in ("p50", "p95", "p99"):
        assert snap[f"serve_ttft_{q}_s"] == pytest.approx(
            s["ttft"][q], abs=1e-6
        )
        assert snap[f"serve_itl_{q}_s"] == pytest.approx(
            s["itl"][q], abs=1e-6
        )
    # And the Prometheus rendering carries the observatory keys.
    led.note_serve_ledger(
        {"idle": 0.5, "decode": 0.3, "prefill": 0.1, "insert": 0.05,
         "host_sched": 0.05},
        utilization=0.8,
        masked_waste=0.125,
        slo_violations=3,
    )
    snap = led.snapshot()
    assert snap["serve_idle_fraction"] == 0.5
    assert snap["serve_decode_utilization"] == 0.8
    assert snap["serve_masked_row_waste"] == 0.125
    assert snap["serve_slo_violations"] == 3
    text = prometheus_text(snap)
    assert "tpuflow_serve_idle_fraction 0.5" in text
    assert "tpuflow_serve_decode_fraction 0.3" in text
    assert "tpuflow_serve_prefill_fraction 0.1" in text
    assert "tpuflow_serve_decode_utilization 0.8" in text
    assert "tpuflow_serve_masked_row_waste 0.125" in text
    assert "tpuflow_serve_slo_violations_total 3" in text
    assert "tpuflow_serve_itl_p99_seconds" in text
    assert "tpuflow_serve_ttft_p95_seconds" in text


# ------------------------------------------------------------------ CLI
def test_serve_summary_cli(tmp_path, capsys):
    """`python -m tpuflow.obs serve-summary <run_dir>`: human + --json
    modes over the access log, with the ledger gauges folded in from
    the event stream when present; jax-free, mid-run safe."""
    from tpuflow.obs.__main__ import main as obs_main

    run_dir = str(tmp_path / "run")
    log = sl.AccessLog(os.path.join(run_dir, "obs"), proc=0)
    log.write(_mk_record(0, "fp.plain", ttft=0.01, itl=(0.002,)))
    log.write(_mk_record(1, "int8.plain", ttft=0.05, itl=(0.003,), slo=1))
    # Ledger gauges ride the event fragments.
    with open(
        os.path.join(run_dir, "obs", "events.p00000.jsonl"), "w"
    ) as f:
        for name, v in (
            ("serve.idle_fraction", 0.25),
            ("serve.decode_fraction", 0.60),
            ("serve.prefill_fraction", 0.10),
            ("serve.decode_utilization", 0.9),
        ):
            f.write(json.dumps(
                {"kind": "gauge", "name": name, "ts": 1.0, "value": v}
            ) + "\n")
    assert obs_main(["serve-summary", run_dir, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["requests"] == 2
    assert out["slo_violations"] == 1
    assert out["by_group"]["int8.plain"]["ttft"]["p50"] == pytest.approx(
        0.05
    )
    assert out["ledger"]["serve.decode_fraction"] == pytest.approx(0.60)
    # Human mode prints the tables.
    assert obs_main(["serve-summary", run_dir]) == 0
    text = capsys.readouterr().out
    assert "requests: 2" in text
    assert "ttft:" in text and "itl:" in text
    assert "int8.plain" in text
    assert "decode: 60.0%" in text
    # Empty / bad usage exit non-zero with a message, not a trace.
    assert obs_main(["serve-summary", str(tmp_path / "empty")]) == 1
    assert obs_main(["serve-summary"]) == 2
    assert obs_main(["serve-summary", run_dir, "--bogus"]) == 2
