"""tpuflow.obs tests: recorder schema, gang-worker merge, disabled-path
overhead, buffered flushing, catalog lint, timeline card, and the
end-to-end flow dryrun producing a merged run timeline (ISSUE 1
acceptance: step spans + ckpt save bytes/GB/s + data-loader wait +
rendered timeline card)."""

import json
import os
import time

import numpy as np
import pytest

from tpuflow import obs


@pytest.fixture(autouse=True)
def obs_reset(tmp_path, monkeypatch):
    """Every test starts with telemetry off and an isolated home."""
    monkeypatch.setenv("TPUFLOW_HOME", str(tmp_path / "home"))
    monkeypatch.delenv("TPUFLOW_OBS_DIR", raising=False)
    monkeypatch.delenv("TPUFLOW_OBS_PROC", raising=False)
    obs.configure(None)
    yield
    obs.configure(None)


def _events_file(d):
    """The single per-process event file under ``d`` (pid-suffixed)."""
    import glob

    (path,) = glob.glob(os.path.join(d, "events.p*.jsonl"))
    return path


# ----------------------------------------------------------- recorder core
def test_recorder_schema_and_kinds(tmp_path):
    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    with obs.span("flow.step", step="train", task=1):
        pass
    obs.counter("train.tokens", 1024)
    obs.gauge("device.bytes_in_use", 5.0, device=0)
    obs.histogram("train.step_s", 0.01)
    obs.event("train.report", step=1, loss=2.5)
    obs.flush()
    events = obs.read_events(_events_file(d))
    kinds = {e["kind"] for e in events}
    assert kinds == {"span", "counter", "gauge", "histogram", "event"}
    for e in events:
        # The schema contract documented in the README runbook.
        assert {"kind", "name", "ts", "proc", "pid"} <= set(e)
    span = next(e for e in events if e["kind"] == "span")
    assert span["name"] == "flow.step" and span["dur_s"] >= 0
    assert span["step"] == "train" and span["task"] == 1
    ctr = next(e for e in events if e["kind"] == "counter")
    assert ctr["value"] == 1024


def test_span_error_annotation(tmp_path):
    obs.configure(str(tmp_path / "obs"), proc=0)
    with pytest.raises(RuntimeError):
        with obs.span("flow.step", step="boom"):
            raise RuntimeError("x")
    obs.flush()
    (ev,) = obs.read_events(_events_file(str(tmp_path / "obs")))
    assert ev["error"] == "RuntimeError"


def test_gang_worker_merge(tmp_path):
    """Per-process event files union into one time-sorted events.jsonl —
    the gang-worker merge of the acceptance criteria."""
    run_dir = str(tmp_path / "run")
    d = obs.obs_dir(run_dir)
    r0 = obs.Recorder(d, proc=0, flush_interval=60)
    r1 = obs.Recorder(d, proc=1, flush_interval=60)
    r0.record("span", "flow.step", ts=10.0, dur_s=1.0, step="train")
    r1.record("span", "flow.gang_member", ts=9.5, dur_s=0.5, step="train")
    r1.record("counter", "train.tokens", ts=10.5, value=64)
    r0.close()
    r1.close()
    events = obs.merge_run_events(run_dir)
    assert [e["ts"] for e in events] == sorted(e["ts"] for e in events)
    assert {e["proc"] for e in events} == {0, 1}
    # The merged file is committed at the run root and re-readable.
    merged = os.path.join(run_dir, "events.jsonl")
    assert os.path.exists(merged)
    assert obs.read_events(merged) == events
    # load_run_events prefers the committed merge.
    assert obs.load_run_events(run_dir) == events


def test_merge_tiebreak_and_idempotence(tmp_path):
    """Identical-``ts`` events from different gang members merge in a
    stable order (proc breaks the tie; within one file the write order is
    kept by the stable sort) and re-merging is byte-identical — consumers
    diffing two reads of events.jsonl must never see phantom churn."""
    run_dir = str(tmp_path / "run")
    d = obs.obs_dir(run_dir)
    r1 = obs.Recorder(d, proc=1, flush_interval=60)
    r0 = obs.Recorder(d, proc=0, flush_interval=60)
    # Same timestamp everywhere; per-proc write order distinct.
    r1.record("event", "train.report", ts=5.0, seq="p1-first")
    r1.record("event", "train.report", ts=5.0, seq="p1-second")
    r0.record("event", "train.report", ts=5.0, seq="p0-first")
    r0.record("counter", "train.tokens", ts=5.0, value=1)
    r0.close()
    r1.close()
    first = obs.merge_run_events(run_dir)
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        first_bytes = f.read()
    # Ties break by proc; same-proc events keep their file order.
    assert [e["proc"] for e in first] == [0, 0, 1, 1]
    assert [e.get("seq") for e in first if e["proc"] == 1] == [
        "p1-first", "p1-second",
    ]
    # Idempotent: the merged file at the run root is NOT a fragment, so
    # re-merging re-reads only the per-proc files and reproduces the
    # exact same artifact.
    second = obs.merge_run_events(run_dir)
    assert second == first
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        assert f.read() == first_bytes


def test_recorder_buffer_bound_counts_drops(tmp_path):
    """Satellite: the in-memory buffer is bounded; overflowing events are
    counted, and the count surfaces as a final obs.dropped event on
    close instead of vanishing invisibly."""
    d = str(tmp_path / "obs")
    rec = obs.Recorder(d, proc=0, flush_interval=3600, max_buffered=10)
    for i in range(25):
        rec.record("counter", "train.tokens", value=i)
    assert rec.dropped == 15
    rec.close()
    events = obs.read_events(rec.path)
    kept = [e for e in events if e["name"] == "train.tokens"]
    assert len(kept) == 10
    (drop,) = [e for e in events if e["name"] == "obs.dropped"]
    assert drop["value"] == 15
    # A second close must not duplicate the accounting event.
    rec.close()
    assert len(
        [e for e in obs.read_events(rec.path) if e["name"] == "obs.dropped"]
    ) == 1


def test_recorder_failed_flush_counts_lost_batch(tmp_path, monkeypatch):
    """Satellite: an OSError on the append path used to silently lose the
    whole drained batch — now it lands in the drop count."""
    d = str(tmp_path / "obs")
    rec = obs.Recorder(d, proc=0, flush_interval=3600)
    rec.record("counter", "train.tokens", value=1)
    rec.record("counter", "train.tokens", value=2)
    # Make the append path fail: the target becomes a directory.
    os.unlink(rec.path) if os.path.exists(rec.path) else None
    os.makedirs(rec.path)
    rec.flush()
    assert rec.dropped == 2
    os.rmdir(rec.path)  # restore writability for the close-time event
    rec.record("counter", "train.tokens", value=3)
    rec.close()
    events = obs.read_events(rec.path)
    assert [e["value"] for e in events if e["name"] == "train.tokens"] == [3]
    (drop,) = [e for e in events if e["name"] == "obs.dropped"]
    assert drop["value"] == 2


def test_merge_tolerates_torn_tail(tmp_path):
    run_dir = str(tmp_path / "run")
    d = obs.obs_dir(run_dir)
    os.makedirs(d)
    with open(os.path.join(d, "events.p00000.jsonl"), "w") as f:
        f.write(json.dumps({"kind": "event", "name": "x", "ts": 1.0}) + "\n")
        f.write('{"kind": "event", "name": "torn...')  # crashed writer
    events = obs.merge_run_events(run_dir)
    assert len(events) == 1 and events[0]["name"] == "x"


def test_events_buffered_and_flushed_off_hot_path(tmp_path):
    """Acceptance: with obs enabled, events buffer in memory — record()
    does no file I/O; the file appears on flush (or the background
    flusher), not on the caller's thread."""
    d = str(tmp_path / "obs")
    rec = obs.Recorder(d, proc=0, flush_interval=3600)  # flusher dormant
    path = rec.path
    for i in range(100):
        rec.record("counter", "train.tokens", value=i)
    assert not os.path.exists(path) or os.path.getsize(path) == 0
    rec.flush()
    assert len(obs.read_events(path)) == 100
    rec.close()


# ------------------------------------------------------- disabled overhead
def test_disabled_span_is_shared_noop():
    """Disabled-path contract: span() hands back ONE shared no-op context
    manager — no allocation, no recorder touch."""
    assert not obs.enabled()
    s1 = obs.span("train.epoch", epoch=1)
    s2 = obs.span("ckpt.save")
    assert s1 is s2
    with s1 as s:
        s.set(bytes=1)  # attribute API present and inert
    obs.counter("train.tokens", 5)
    obs.histogram("train.step_s", 0.1)
    obs.event("train.report")
    assert obs.recorder() is None


def test_disabled_overhead_unmeasurable_per_step(monkeypatch):
    """Acceptance: with obs disabled, the instrumented hot paths — now
    including the ISSUE 3 health hooks — add no measurable per-step cost.
    The disabled fast path is one module-bool check (plus one ``is not
    None`` for the health monitor); bound it at ~5µs/call (two orders of
    magnitude above its real cost, far below any train step) so the
    guard never flakes."""
    from tpuflow.obs import device as device_mod
    from tpuflow.obs import profcap as profcap_mod
    from tpuflow.obs.health import HealthMonitor
    from tpuflow.train.step import StepClock

    monkeypatch.setenv("TPUFLOW_HEALTH", "0")
    monkeypatch.delenv("TPUFLOW_PROF_TRIGGER", raising=False)
    monitor = HealthMonitor.from_env()
    assert monitor is None  # TPUFLOW_HEALTH=0 removes the monitor
    # Device observatory (ISSUE 15) disarmed paths: the capturer is
    # None without TPUFLOW_PROF_TRIGGER (StepClock pays one `is not
    # None` per fence) and the HBM poller self-disables after the first
    # off-TPU probe (one module-bool check thereafter) — both inside
    # the same µs/call bound as the rest of the hot-path hooks.
    profcap_mod._reset_for_tests()
    assert profcap_mod.maybe_from_env() is None
    device_mod._reset_for_tests()
    device_mod.maybe_emit_hbm(force=True)  # CPU probe → self-disable
    assert device_mod._POLL_OFF
    clock = StepClock()
    assert clock.recording is False
    assert clock._cap is None  # disarmed detector: the one-check path
    n = 10_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("train.epoch"):
            pass
        clock.step_done(tokens=64)
        obs.counter("train.tokens", 64)
        device_mod.maybe_emit_hbm()  # disarmed: one bool check
        # The loops' per-step health gate when both knobs are off: one
        # None check + one bool — they never host-copy the numerics.
        if monitor is not None or clock.recording:
            raise AssertionError("disabled health path took the slow branch")
        # And health_done itself is one bool check when obs is off (the
        # monitor-on, obs-off configuration).
        clock.health_done(
            loss=0.0, grad_norm=0.0, update_norm=0.0, param_norm=0.0,
            nonfinite=False,
        )
    dt = time.perf_counter() - t0
    assert dt < 0.05 * (n / 10_000) * 10, f"disabled obs overhead {dt:.3f}s"
    # Serving observatory (ISSUE 13): the disarmed trace hook is one
    # bool check, and the engine-time ledger's per-phase charges are
    # a couple of monotonic reads — neither can register against a
    # decode block. Pin both at the same generous 5µs/call bound
    # (ServeEngine._trace is exercised unbound so no model/compile is
    # needed here; the armed path is covered in tests/test_serve.py).
    import types

    from tpuflow.infer.serve import ServeEngine
    from tpuflow.obs.serve_ledger import ServeLedger

    shim = types.SimpleNamespace(_trace_on=False)
    led = ServeLedger()  # unarmed: no SLOs declared
    t0 = time.perf_counter()
    for _ in range(n):
        ServeEngine._trace(shim, None, "tick", tokens=1)
        with led.bucket("decode"):
            pass
        led.note_decode_block(8, 4, 4)
        if led.check_ttft(1.0) or led.check_itl(1.0):
            raise AssertionError("unarmed SLO check fired")
    dt = time.perf_counter() - t0
    assert dt < 0.05 * (n / 10_000) * 10, (
        f"disabled serve trace/ledger overhead {dt:.3f}s"
    )
    # timed_iter must return the iterable UNTOUCHED when disabled (no
    # generator frame on the loader hot path).
    loader = [1, 2, 3]
    assert obs.timed_iter(loader, "data.batch_wait_s") is loader
    # ISSUE 6 surfaces stay opt-in on the disabled path: no export server
    # without the env knob, no flight artifact without a recorder.
    monkeypatch.delenv("TPUFLOW_OBS_HTTP_PORT", raising=False)
    from tpuflow.obs import export as obs_export
    from tpuflow.obs import flight as flight_mod

    assert obs_export.maybe_start_from_env(proc=0) is None
    assert flight_mod.dump_flight("noop") is None


# ------------------------------------------------------------ catalog lint
def test_obs_catalog_lint():
    """Every literal emitter name in tpuflow/ is registered in the
    catalog with the right kind (tools/obs_lint.py as a pytest check)."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "obs_lint", os.path.join(repo, "tools", "obs_lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    errors, _warnings = mod.lint(repo)
    assert not errors, "\n".join(errors)
    # And the emitters actually cover every subsystem the ISSUE names.
    kinds = {(k, n) for _, k, n in mod.emitted_names(repo)}
    for required in (
        ("span", "flow.step"),
        ("span", "ckpt.save"),
        ("span", "ckpt.restore"),
        ("histogram", "data.batch_wait_s"),
        ("histogram", "train.step_s"),
        ("span", "infer.generate"),
        ("counter", "infer.spec.committed"),
        # Async step pipeline (ISSUE 4) with the right kinds.
        ("gauge", "data.host_wait_s"),
        ("gauge", "train.dispatch_depth"),
        ("counter", "data.prefetch_hit"),
        ("counter", "data.prefetch_miss"),
        # Training-health observatory (ISSUE 3) with the right kinds.
        ("gauge", "health.loss"),
        ("gauge", "health.grad_norm"),
        ("gauge", "health.update_norm"),
        ("gauge", "health.param_norm"),
        ("counter", "health.nonfinite"),
        ("event", "health.anomaly"),
        ("event", "health.rollback"),
        ("event", "health.profile"),
        # Run observatory (ISSUE 6) with the right kinds.
        ("gauge", "goodput.productive_s"),
        ("gauge", "goodput.lost_s"),
        ("gauge", "goodput.fraction"),
        ("event", "obs.flight"),
        ("event", "obs.export"),
        # Continuous-batching serving engine (ISSUE 8) with the right
        # kinds (also enforced via REQUIRED_EMITTERS below — same
        # standalone-tool/pytest-twin cross-check as the ckpt names).
        ("gauge", "serve.queue_depth"),
        ("gauge", "serve.slot_occupancy"),
        ("gauge", "serve.ttft_s"),
        ("gauge", "serve.tokens_per_s"),
        ("counter", "serve.tokens"),
        ("counter", "serve.requests"),
        ("span", "serve.admit"),
        ("event", "serve.complete"),
        ("span", "serve.warmup"),
        ("span", "serve.prefill"),
        ("span", "serve.decode"),
        # Paged KV serving (ISSUE 11) with the right kinds (also
        # REQUIRED_EMITTERS below — same standalone/pytest cross-check).
        ("gauge", "serve.pages_free"),
        ("gauge", "serve.prefix_hits"),
        ("gauge", "serve.spec_accept_rate"),
        ("event", "serve.page_evict"),
        # Serving observatory (ISSUE 13) with the right kinds (also
        # REQUIRED_EMITTERS below — same standalone/pytest cross-check):
        # lifecycle traces, SLO accounting, engine-time ledger gauges.
        ("event", "serve.trace"),
        ("event", "serve.slo_violation"),
        ("counter", "serve.slo_violations"),
        ("gauge", "serve.idle_fraction"),
        ("gauge", "serve.decode_fraction"),
        ("gauge", "serve.prefill_fraction"),
        ("gauge", "serve.decode_utilization"),
        ("gauge", "serve.masked_row_waste"),
        # Disaggregated prefill/decode + tiered KV (ISSUE 19) with the
        # right kinds (also REQUIRED_EMITTERS below — same
        # standalone/pytest cross-check): ship/import spans, the tier
        # spill/hit/promote trail, per-tier page gauges.
        ("span", "serve.kv_ship"),
        ("span", "serve.kv_import"),
        ("event", "serve.tier_hit"),
        ("event", "serve.tier_promote"),
        ("event", "serve.tier_spill"),
        ("gauge", "serve.pages_host"),
        ("gauge", "serve.pages_disk"),
        # Fleet observatory (ISSUE 14) with the right kinds (also
        # REQUIRED_EMITTERS below — same standalone/pytest cross-check):
        # registration, the poll sweep, staleness evidence.
        ("event", "fleet.register"),
        ("span", "fleet.poll"),
        ("gauge", "fleet.size"),
        ("gauge", "fleet.qps"),
        ("event", "fleet.replica_stale"),
        # Device observatory (ISSUE 15) with the right kinds (also
        # REQUIRED_EMITTERS below — same standalone/pytest cross-check):
        # program ledger, HBM gauges, budget verdicts, triggered capture.
        ("event", "device.program"),
        ("gauge", "device.hbm_used"),
        ("gauge", "device.hbm_peak"),
        ("gauge", "device.hbm_limit"),
        ("event", "device.hbm_budget"),
        ("event", "prof.capture"),
        # Decision observatory (ISSUE 16) with the right kinds (also
        # REQUIRED_EMITTERS below — same standalone/pytest cross-check):
        # the registry's append audit, the alert lifecycle edges.
        ("event", "registry.append"),
        ("event", "alert.fired"),
        ("event", "alert.resolved"),
        # Front-door router (ISSUE 17) with the right kinds (also
        # REQUIRED_EMITTERS below — same standalone/pytest cross-check):
        # admission, failover, drain, and autoscale evidence.
        ("event", "router.admit"),
        ("event", "router.reject"),
        ("event", "router.retry"),
        ("event", "router.reroute"),
        ("event", "router.drain"),
        ("event", "router.replace"),
        ("gauge", "router.queue_depth"),
        ("gauge", "router.budget_pages"),
        # Disaggregated serving (ISSUE 19): the router's ship hop and
        # its explicit local-prefill degradation.
        ("event", "router.ship"),
        ("event", "router.ship_fallback"),
        # End-to-end tracing (ISSUE 18) with the right kinds (also
        # REQUIRED_EMITTERS below — same standalone/pytest cross-check):
        # tail-sampling escalations, per-flush evidence, and the
        # spans-written/spans-dropped conservation pair.
        ("event", "trace.escalate"),
        ("event", "trace.flush"),
        ("counter", "trace.spans"),
        ("counter", "trace.dropped"),
        # Native int8 decode (ISSUE 9) with the right kinds (also
        # REQUIRED_EMITTERS below — same standalone/pytest cross-check).
        ("counter", "serve.quant_requests"),
        ("event", "quant.decision"),
        ("event", "quant.kernel_fallback"),
        # Raise-MFU step work (ISSUE 10) with the right kinds (also
        # REQUIRED_EMITTERS below — same standalone/pytest cross-check).
        ("event", "ops.flash_bwd_fused"),
        ("event", "train.remat_policy"),
        ("gauge", "train.exposed_comm_s"),
        ("gauge", "train.comm_overlap_s"),
        # Durable checkpointing (ISSUE 5) — the lint itself also enforces
        # these via REQUIRED_EMITTERS; asserting through both keeps the
        # standalone tool and the pytest twin honest about each other.
        *mod.REQUIRED_EMITTERS,
    ):
        assert required in kinds, f"missing emitter {required}"
    # Kind mismatches and dynamic (unlintable) names are errors, not just
    # name-presence checks.
    assert mod.dynamic_name_calls('obs.gauge(f"health.{k}", v)')
    assert mod.dynamic_name_calls("obs.event(name, step=1)")
    assert not mod.dynamic_name_calls('obs.gauge("health.loss", v)')
    assert not mod.dynamic_name_calls('obs.gauge(\n    "health.loss", v)')


def test_summarize_aggregates():
    events = [
        {"kind": "span", "name": "ckpt.save", "ts": 1.0, "dur_s": 2.0,
         "bytes": 4e9, "gbps": 2.0},
        {"kind": "span", "name": "ckpt.restore", "ts": 5.0, "dur_s": 1.0,
         "bytes": 1e9},
        {"kind": "counter", "name": "train.tokens", "ts": 2.0, "value": 100},
        {"kind": "histogram", "name": "train.step_s", "ts": 2.1,
         "value": 0.5},
        {"kind": "histogram", "name": "train.step_s", "ts": 2.2,
         "value": 1.5},
        {"kind": "counter", "name": "data.prefetch_hit", "ts": 2.3,
         "value": 3},
        {"kind": "counter", "name": "data.prefetch_miss", "ts": 2.4,
         "value": 1},
    ]
    s = obs.summarize(events)
    assert s["spans"]["ckpt.save"]["count"] == 1
    assert s["counters"]["train.tokens"] == 100
    assert s["histograms"]["train.step_s"]["count"] == 2
    h = s["headline"]
    assert h["ckpt_save_gbps"] == pytest.approx(2.0)
    assert h["ckpt_restore_gbps"] == pytest.approx(1.0)
    assert h["tokens_per_s"] == pytest.approx(100 / 2.0)
    assert h["prefetch_hit_rate"] == pytest.approx(0.75)


def test_timeline_card_renders(tmp_path):
    from tpuflow.flow.cards import CardBuffer, timeline_card

    events = [
        {"kind": "span", "name": "flow.run", "ts": 0.0, "dur_s": 10.0,
         "proc": 0},
        {"kind": "span", "name": "flow.step", "ts": 0.1, "dur_s": 8.0,
         "proc": 0, "step": "train"},
        {"kind": "span", "name": "ckpt.save", "ts": 6.0, "dur_s": 1.0,
         "proc": 0, "bytes": 2e9, "gbps": 2.0},
        {"kind": "histogram", "name": "train.step_s", "ts": 2.0,
         "value": 0.2, "proc": 0},
    ]
    buf = CardBuffer()
    timeline_card(buf, events)
    html = buf.render_html("t")
    assert "Run timeline" in html
    assert "ckpt.save" in html and "2.00 GB/s" in html
    assert "train.step_s" in html
    # flow.run is the envelope — not drawn as its own bar.
    assert "flow.run [" not in html


# ------------------------------------------------- end-to-end flow dryrun
def _read_run_events(run_dir):
    path = os.path.join(run_dir, "events.jsonl")
    assert os.path.exists(path), f"no merged events.jsonl in {run_dir}"
    return obs.read_events(path)


@pytest.mark.slow
def test_gpt_flow_dryrun_produces_timeline(tmp_path, monkeypatch):
    """The acceptance dryrun on the REAL flow file: flows/gpt_flow.py run
    with the test preset produces a merged events.jsonl + timeline card."""
    import importlib
    import sys

    flows_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "flows"
    )
    monkeypatch.syspath_prepend(flows_dir)
    sys.modules.pop("gpt_flow", None)
    gpt_flow = importlib.import_module("gpt_flow")
    from tpuflow.flow.runner import FlowRunner

    runner = FlowRunner(gpt_flow.TpuGptTrain)
    pathspec = runner.run(
        {
            "preset": "test", "epochs": 1, "steps_per_epoch": 2,
            "batch_size": 8, "seq_len": 16, "learning_rate": 1e-3,
            "data_axis": 4, "fsdp_axis": 2, "tensor_axis": 1, "seq_axis": 1,
            "expert_axis": 1, "experts": 0, "stage_axis": 1,
            "microbatches": 2, "attn_impl": "xla", "dataset": "lm_synth",
            "from_run": "", "sample_tokens": 4, "accum_steps": 1,
            "optimizer": "adamw", "lr_schedule": "constant",
            "warmup_steps": 0, "grad_clip": 0.0, "weight_decay": 1e-4,
            "ema_decay": 0.0, "ckpt_dtype": "", "decay_steps": 0,
            "remat_policy": "", "dtype": "",
        }
    )
    from tpuflow.flow import Run, store

    run_dir = store.run_dir(*pathspec.split("/"))
    events = _read_run_events(run_dir)
    names = {(e["kind"], e["name"]) for e in events}
    assert ("span", "flow.step") in names
    assert ("span", "ckpt.save") in names
    assert ("histogram", "data.batch_wait_s") in names
    assert ("span", "infer.generate") in names  # sample_tokens leg
    save = next(e for e in events if e["name"] == "ckpt.save")
    assert save["bytes"] > 0 and save["gbps"] > 0
    assert os.path.exists(os.path.join(run_dir, "timeline.html"))
    # The client accessor reads the same stream + headline.
    run = Run(pathspec)
    t = run.telemetry()
    assert t["headline"]["ckpt_save_gbps"] > 0
    assert run.meta["telemetry"]["ckpt_save_gbps"] > 0


def test_flow_run_produces_merged_timeline(tmp_path):
    """Tier-1 twin of the dryrun: a small flow that trains through the
    trainer + checkpoint + prefetching loader produces the merged
    events.jsonl with step/ckpt/data evidence and the timeline card."""
    import jax

    from tpuflow.flow import FlowSpec, Run, step, store
    from tpuflow.flow.runner import FlowRunner

    class ObsFlow(FlowSpec):
        @step
        def start(self):
            from tpuflow import dist
            from tpuflow.ckpt import CheckpointManager
            from tpuflow.data.datasets import Split
            from tpuflow.data.loader import ShardedLoader, prefetch_to_device
            from tpuflow.flow.spec import current

            mesh = dist.make_mesh({"data": 8})
            rng = np.random.default_rng(0)
            split = Split(
                images=rng.standard_normal((32, 4)).astype(np.float32),
                labels=rng.integers(0, 2, 32).astype(np.int64),
            )
            loader = ShardedLoader(split, batch_size=8)
            total = 0.0
            for b in prefetch_to_device(loader, mesh, keys=("x", "y")):
                total += float(jax.numpy.sum(b["x"]))
            self.total = total
            mgr = CheckpointManager(
                os.path.join(current.tpu_storage_path, "ckpt"),
                async_save=True,
            )
            state = {"w": np.arange(1024, dtype=np.float32)}
            mgr.save(1, state, metrics={"val_loss": 1.0})
            mgr.wait_until_finished()
            restored = mgr.restore(1)
            assert np.allclose(restored["w"], state["w"])
            mgr.close()
            self.next(self.end)

        @step
        def end(self):
            pass

    pathspec = FlowRunner(ObsFlow).run({})
    run_dir = store.run_dir(*pathspec.split("/"))
    events = _read_run_events(run_dir)
    names = {(e["kind"], e["name"]) for e in events}
    assert ("span", "flow.run") in names
    assert ("span", "flow.step") in names
    assert ("span", "ckpt.save") in names
    assert ("span", "ckpt.restore") in names
    assert ("histogram", "data.batch_wait_s") in names
    save = next(e for e in events if e["name"] == "ckpt.save")
    assert save["bytes"] == 1024 * 4
    assert save["gbps"] > 0
    restore = next(e for e in events if e["name"] == "ckpt.restore")
    assert restore["bytes"] == 1024 * 4
    # Steps are attributed: both flow steps appear with their names.
    steps = {e.get("step") for e in events if e["name"] == "flow.step"}
    assert steps == {"start", "end"}
    assert os.path.exists(os.path.join(run_dir, "timeline.html"))
    with open(os.path.join(run_dir, "timeline.html")) as f:
        html = f.read()
    assert "Run timeline" in html and "ckpt.save" in html
    # Client accessors.
    run = Run(pathspec)
    assert ("span", "ckpt.save") in {
        (e["kind"], e["name"]) for e in run.events()
    }
    assert run.telemetry()["headline"]["ckpt_save_gbps"] > 0
    # Recording is scoped to the run: the recorder is closed afterwards.
    assert not obs.enabled()


def test_flow_obs_disabled_by_env(tmp_path, monkeypatch):
    """TPUFLOW_OBS=0 turns the whole stream off: no obs dir, no merged
    events, no timeline card, no telemetry in run.json."""
    monkeypatch.setenv("TPUFLOW_OBS", "0")
    from tpuflow.flow import store
    from tpuflow.flow.runner import FlowRunner
    from tpuflow.flow.spec import FlowSpec, step

    class Tiny(FlowSpec):
        @step
        def start(self):
            self.next(self.end)

        @step
        def end(self):
            pass

    pathspec = FlowRunner(Tiny).run({})
    run_dir = store.run_dir(*pathspec.split("/"))
    assert not os.path.exists(os.path.join(run_dir, "events.jsonl"))
    assert not os.path.exists(os.path.join(run_dir, "timeline.html"))
    assert store.read_run_meta(*pathspec.split("/"))["telemetry"] == {}


# ------------------------------------------------- goodput ledger (ISSUE 6)
def test_goodput_buckets_sum_to_wall_and_classify():
    """The interval sweep charges every instant to exactly one bucket:
    data waits are carved OUT of the step fence containing them, async
    checkpoint saves charge only their exposed (non-overlapped) tail,
    and the gap between attempt lanes is the requeue bucket — so the
    buckets sum to the measured wall by construction."""
    T = 1000.0
    events = [
        {"kind": "span", "name": "train.compile", "ts": T + 0.0,
         "dur_s": 2.0, "proc": 0, "launch": 0},
        {"kind": "histogram", "name": "train.step_s", "ts": T + 3.0,
         "value": 1.0, "proc": 0, "launch": 0},
        {"kind": "gauge", "name": "data.host_wait_s", "ts": T + 3.6,
         "value": 0.4, "proc": 0, "launch": 0},
        {"kind": "histogram", "name": "train.step_s", "ts": T + 4.0,
         "value": 1.0, "proc": 0, "launch": 0},
        # Async save overlapping the second step; only [4.0, 4.5] exposed.
        {"kind": "span", "name": "ckpt.save", "ts": T + 3.5, "dur_s": 1.0,
         "proc": 0, "launch": 0},
        # Requeued attempt: restore then one more step, after a 2 s gap.
        {"kind": "span", "name": "ckpt.restore", "ts": T + 6.5,
         "dur_s": 0.5, "proc": 0, "launch": 1},
        {"kind": "histogram", "name": "train.step_s", "ts": T + 8.0,
         "value": 1.0, "proc": 0, "launch": 1},
    ]
    gp = obs.compute_goodput(events)
    b = gp["buckets"]
    assert gp["wall_s"] == pytest.approx(8.0)
    assert b["compile"] == pytest.approx(2.0)
    assert b["step"] == pytest.approx(2.6)       # 3.0 fenced − 0.4 wait
    assert b["data_wait"] == pytest.approx(0.4)
    assert b["ckpt"] == pytest.approx(0.5)       # exposed tail only
    assert b["restore"] == pytest.approx(0.5)
    assert b["requeue_gap"] == pytest.approx(2.0)
    assert b["other"] == pytest.approx(0.0)
    assert sum(b.values()) == pytest.approx(gp["wall_s"])
    assert gp["fraction"] == pytest.approx(2.6 / 8.0)
    assert gp["steps_timed"] == 3
    assert [a["attempt"] for a in gp["attempts"]] == [0, 1]
    assert gp["attempts"][1]["start_s"] == pytest.approx(6.5)
    # And summarize embeds the same ledger + headline fraction.
    s = obs.summarize(events)
    assert s["goodput"]["buckets"]["requeue_gap"] == pytest.approx(2.0)
    assert s["headline"]["goodput_fraction"] == pytest.approx(0.325)
    assert s["headline"]["requeue_gap_s"] == pytest.approx(2.0)


def test_goodput_replayed_steps_are_not_productive():
    """After a health.rollback (from_step − step discarded steps), the
    next that-many fenced steps re-cover old ground: charged to the
    replay bucket, not the productive one."""
    events = [
        {"kind": "histogram", "name": "train.step_s", "ts": 1.0,
         "value": 1.0, "proc": 0},
        {"kind": "event", "name": "health.rollback", "ts": 1.5,
         "step": 2, "from_step": 4, "proc": 0},
        {"kind": "histogram", "name": "train.step_s", "ts": 3.0,
         "value": 1.0, "proc": 0},
        {"kind": "histogram", "name": "train.step_s", "ts": 4.0,
         "value": 1.0, "proc": 0},
        {"kind": "histogram", "name": "train.step_s", "ts": 5.0,
         "value": 1.0, "proc": 0},
    ]
    gp = obs.compute_goodput(events)
    assert gp["buckets"]["replay"] == pytest.approx(2.0)
    assert gp["buckets"]["step"] == pytest.approx(2.0)
    assert sum(gp["buckets"].values()) == pytest.approx(gp["wall_s"])


def test_goodput_empty_and_partial_streams():
    assert obs.compute_goodput([]) == {
        "wall_s": 0.0, "fraction": 0.0,
        "buckets": {b: 0.0 for b in obs.GOODPUT_BUCKETS},
        "attempts": [], "steps_timed": 0,
    }
    # Events without usable timestamps are skipped, not fatal.
    gp = obs.compute_goodput([{"kind": "event", "name": "x"}])
    assert gp["wall_s"] == 0.0


# --------------------------------------- live ledger + export (ISSUE 6)
def test_live_ledger_and_metrics_endpoint(tmp_path):
    """StepClock fences feed the in-process ledger; the export server
    serves it as Prometheus text (/metrics) and JSON (/status) without
    touching any file."""
    import urllib.error
    import urllib.request

    from tpuflow.obs import export as obs_export
    from tpuflow.obs import goodput
    from tpuflow.train.step import StepClock

    obs.configure(str(tmp_path / "obs"), proc=0)
    clock = StepClock()  # resets the live ledger for "this leg"
    goodput.live().set_model_flops_per_token(6.0 * 1000)
    time.sleep(0.005)  # give the fences real (ms-scale) durations
    clock.compile_done()
    for i in range(3):
        time.sleep(0.002)
        clock.step_done(tokens=64, step=i + 1)
    clock.health_done(
        loss=1.25, grad_norm=0.5, update_norm=0.1, param_norm=2.0,
        nonfinite=False,
    )
    snap = goodput.live().snapshot()
    assert snap["steps"] == 3 and snap["step"] == 3
    assert snap["tokens"] == 192
    assert snap["compile_s"] > 0 and snap["productive_s"] > 0
    assert 0.0 <= snap["goodput_fraction"] <= 1.0
    srv = obs_export.MetricsServer(port=0)
    try:
        with urllib.request.urlopen(f"{srv.url}/metrics", timeout=5) as r:
            text = r.read().decode()
        assert "tpuflow_steps_total 3" in text
        assert "tpuflow_tokens_total 192" in text
        assert "tpuflow_goodput_fraction" in text
        assert "tpuflow_loss 1.25" in text
        assert "# TYPE tpuflow_steps_total counter" in text
        with urllib.request.urlopen(f"{srv.url}/status", timeout=5) as r:
            st = json.loads(r.read().decode())
        assert st["steps"] == 3 and st["pid"] == os.getpid()
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{srv.url}/nope", timeout=5)
    finally:
        srv.close()
    # The periodic goodput gauges landed in the event stream (one at the
    # compile fence at minimum).
    obs.flush()
    names = {e["name"] for e in obs.read_events(_events_file(str(tmp_path / "obs")))}
    assert "goodput.productive_s" in names
    assert "goodput.lost_s" in names and "goodput.fraction" in names


def test_export_opt_in_member_zero_and_singleton(monkeypatch):
    from tpuflow.obs import export as obs_export

    monkeypatch.delenv("TPUFLOW_OBS_HTTP_PORT", raising=False)
    assert obs_export.maybe_start_from_env(proc=0) is None  # opt-in only
    monkeypatch.setenv("TPUFLOW_OBS_HTTP_PORT", "0")
    assert obs_export.maybe_start_from_env(proc=1) is None  # member 0 only
    srv = obs_export.maybe_start_from_env(proc=0)
    try:
        assert srv is not None and srv.port > 0
        assert obs_export.maybe_start_from_env(proc=0) is srv  # idempotent
    finally:
        obs_export.stop()
    monkeypatch.setenv("TPUFLOW_OBS_HTTP_PORT", "nope")
    assert obs_export.maybe_start_from_env(proc=0) is None  # malformed


# ------------------------------------------------ flight recorder (ISSUE 6)
def test_flight_dump_ring_fingerprint_and_marker(tmp_path):
    from tpuflow.obs import flight

    d = str(tmp_path / "obs")
    obs.configure(d, proc=3)
    for i in range(300):
        obs.counter("train.tokens", i)
    try:
        raise RuntimeError("boom")
    except RuntimeError as e:
        path = flight.dump_flight("unhandled_exception", e)
    assert path == flight.flight_path(d, 3)
    with open(path) as f:
        dump = json.load(f)
    assert dump["reason"] == "unhandled_exception"
    assert dump["proc"] == 3 and dump["pid"] == os.getpid()
    assert "RuntimeError: boom" in dump["stack"]
    # Bounded ring: 300 events recorded, the newest 256 kept.
    assert len(dump["events"]) == 256
    assert dump["events"][-1]["name"] == "train.tokens"
    assert dump["events"][-1]["value"] == 299
    assert any(k.startswith("TPUFLOW_") for k in dump["env"])
    # The marker event landed in the stream, pointing at the artifact.
    obs.flush()
    events = obs.read_events(_events_file(d))
    (marker,) = [e for e in events if e["name"] == "obs.flight"]
    assert marker["path"] == path
    # Re-dump overwrites atomically (newest wins).
    assert flight.dump_flight("sigterm") == path
    with open(path) as f:
        assert json.load(f)["reason"] == "sigterm"


def test_recorder_stamps_attempt_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUFLOW_ATTEMPT", "2")
    rec = obs.Recorder(str(tmp_path / "obs"), proc=1, flush_interval=60)
    rec.record("counter", "train.tokens", value=1)
    rec.close()
    (ev,) = [
        e for e in obs.read_events(rec.path) if e["name"] == "train.tokens"
    ]
    assert ev["launch"] == 2


# ------------------------------------------------------ CLI (ISSUE 6)
def test_obs_cli_summarize(tmp_path, capsys):
    run_dir = str(tmp_path / "run")
    rec = obs.Recorder(obs.obs_dir(run_dir), proc=0, flush_interval=60)
    rec.record("span", "train.compile", ts=100.0, dur_s=1.0)
    rec.record("histogram", "train.step_s", ts=102.0, value=0.5)
    rec.record("histogram", "train.step_s", ts=103.0, value=0.5)
    rec.record("counter", "train.tokens", ts=103.0, value=256)
    rec.close()
    from tpuflow.obs.__main__ import main as obs_main

    assert obs_main(["summarize", run_dir, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["headline"]["steps_timed"] == 2
    assert out["goodput"]["steps_timed"] == 2
    assert out["goodput"]["buckets"]["step"] == pytest.approx(1.0)
    assert out["goodput"]["buckets"]["compile"] == pytest.approx(1.0)
    # Human-readable mode prints the decomposition.
    assert obs_main(["summarize", run_dir]) == 0
    text = capsys.readouterr().out
    assert "goodput:" in text and "compile" in text
    # Bad usage / empty runs exit non-zero with a message, not a trace.
    assert obs_main([]) == 2
    assert obs_main(["summarize", run_dir, "--bogus"]) == 2
    assert obs_main(["summarize", str(tmp_path / "empty")]) == 1


# ---------------------------------------- heartbeat step stamp (ISSUE 6)
def test_heartbeat_stamps_step_and_supervisor_reads_it(
    tmp_path, monkeypatch
):
    from tpuflow.flow.runner import FlowRunner
    from tpuflow.utils import heartbeat

    hb = tmp_path / "heartbeat_0"
    monkeypatch.setenv("TPUFLOW_HEARTBEAT_FILE", str(hb))
    heartbeat.beat(step=7)
    assert hb.read_text() == "7"
    before = os.path.getmtime(hb)
    time.sleep(0.01)
    heartbeat.beat()  # plain liveness stamp keeps the last step...
    assert hb.read_text() == "7"
    assert os.path.getmtime(hb) >= before  # ...but refreshes the mtime
    assert FlowRunner._heartbeat_step(str(tmp_path), 0) == 7
    assert FlowRunner._heartbeat_step(str(tmp_path), 1) is None  # absent
    hb.write_text("")  # step-less legacy stamp → no step, no crash
    assert FlowRunner._heartbeat_step(str(tmp_path), 0) is None


# ------------------------------------- tier-1 duration guard (ISSUE 6)
def test_tier1_duration_guard(tmp_path):
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "obs_lint_guard", os.path.join(repo, "tools", "obs_lint.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    path = tmp_path / mod.TIER1_DURATION_FILE

    def write(rec):
        path.write_text(json.dumps(rec))

    assert mod.tier1_duration_guard(str(tmp_path)) is None  # no record
    write({"duration_s": 700.0, "markexpr": "not slow",
           "testscollected": 300})
    assert mod.tier1_duration_guard(str(tmp_path)) is None  # under guard
    write({"duration_s": 860.0, "markexpr": "not slow",
           "testscollected": 300})
    err = mod.tier1_duration_guard(str(tmp_path))
    assert err and "860" in err and "820" in err
    # The slow suite and partial runs are exempt — their durations say
    # nothing about the tier-1 budget.
    write({"duration_s": 9000.0, "markexpr": "slow",
           "testscollected": 20})
    assert mod.tier1_duration_guard(str(tmp_path)) is None
    write({"duration_s": 9000.0, "markexpr": "not slow",
           "testscollected": 5})
    assert mod.tier1_duration_guard(str(tmp_path)) is None
    path.write_text("not json{")  # torn record must not fail the lint
    assert mod.tier1_duration_guard(str(tmp_path)) is None
    # And the guard is wired into lint(): an over-budget record turns
    # into a lint error on the real tree.
    write({"duration_s": 860.0, "markexpr": "not slow",
           "testscollected": 300})
    # lint(root) reads the duration file from its root argument — point a
    # fake root at tmp_path? lint also walks tpuflow/, so run the guard
    # integration through the errors list of a real lint with the record
    # injected beside the real repo is too invasive; the unit coverage
    # above plus the call-site wiring (lint appends tier1_duration_guard)
    # is pinned by reading the source.
    import inspect

    assert "tier1_duration_guard(root)" in inspect.getsource(mod.lint)


def test_trainer_report_and_fit_events(tmp_path):
    """TrainContext.report + Trainer.fit emit into a configured stream."""
    from tpuflow.train import (
        RunConfig,
        ScalingConfig,
        Trainer,
        get_context,
    )

    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)

    def loop(cfg):
        ctx = get_context()
        ctx.report({"val_loss": 1.5}, step=1)

    Trainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=str(tmp_path / "runs")),
    ).fit()
    obs.flush()
    events = obs.read_events(_events_file(d))
    names = {e["name"] for e in events}
    assert "train.fit" in names
    report = next(e for e in events if e["name"] == "train.report")
    assert report["step"] == 1 and report["val_loss"] == 1.5


@pytest.mark.parametrize(
    "device_kind,peak",
    [
        ("TPU v5 lite", 197e12),  # what a v5e chip reports
        ("TPU v5litepod", 197e12),  # the pod-slice spelling
        ("TPU v5p", 459e12),
        ("TPU v5", 459e12),  # must not shadow the lite entries above it
        ("TPU v4", 275e12),
        ("TPU v6 lite", 918e12),
        ("TPU v6e", 918e12),
        ("TPU v9 hyperchip", None),  # unknown: an error, not a default
    ],
)
def test_peak_flops_table_matches_device_kind_strings(device_kind, peak):
    """The rolling MFU's denominator keys on
    ``jax.devices()[0].device_kind``, which reads like 'TPU v5 lite', not
    'v5e': the package's one peak table against the strings each
    generation reports."""
    from tpuflow.obs import goodput

    if peak is None:
        with pytest.raises(ValueError, match=device_kind):
            goodput.table_value(goodput._PEAK_FLOPS, device_kind, "peak")
    else:
        assert goodput.table_value(
            goodput._PEAK_FLOPS, device_kind, "peak"
        ) == peak
