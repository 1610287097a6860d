"""Device observatory (tpuflow.obs.device + tpuflow.obs.profcap,
ISSUE 15), host-pure layer: graceful off-TPU degradation of the
cost/memory analyses and HBM polling (driven through INJECTED device /
compiled objects — no backend dependence), the programs.json
merge-by-name round trip, the static HBM budget check, the capture
governor (exactly-one / cooldown / cap, injected tracer + clock), the
fleet HBM-headroom aggregation, and the jax-free device-summary CLI.
The engine-integration acceptance (shared warmed engine, compile_stats
coverage + invariance) lives in tests/test_serve.py."""

import json
import os

import pytest

from tpuflow import obs
from tpuflow.obs import device as device_mod
from tpuflow.obs import profcap as profcap_mod
from tpuflow.obs.export import prometheus_text
from tpuflow.obs.goodput import ProcessLedger


@pytest.fixture(autouse=True)
def device_obs_reset(monkeypatch):
    """Isolated module state: telemetry off, poller re-armed, capturer
    singleton cleared, warn-once sets cleared."""
    obs.configure(None)
    device_mod._reset_for_tests()
    profcap_mod._reset_for_tests()
    yield
    obs.configure(None)
    device_mod._reset_for_tests()
    profcap_mod._reset_for_tests()


def _events(d):
    import glob

    out = []
    for path in glob.glob(os.path.join(d, "events.p*.jsonl")):
        out.extend(obs.read_events(path))
    return out


# ---------------------------------------------------- injected doubles
class _FakeMem:
    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class _FakeCompiled:
    """Stands in for jax.stages.Compiled: list-of-dict cost analysis
    (the CPU backend's real shape) + attribute-style memory analysis."""

    def __init__(self, flops=1e9, accessed=2e9, arg=100, out=50, temp=30,
                 cost_raises=False, mem_returns_none=False,
                 mem_raises=False):
        self._flops = flops
        self._accessed = accessed
        self._arg, self._out, self._temp = arg, out, temp
        self._cost_raises = cost_raises
        self._mem_none = mem_returns_none
        self._mem_raises = mem_raises

    def cost_analysis(self):
        if self._cost_raises:
            raise NotImplementedError("no cost analysis here")
        return {"flops": self._flops, "bytes accessed": self._accessed}

    def memory_analysis(self):
        if self._mem_raises:
            raise RuntimeError("no memory analysis here")
        if self._mem_none:
            return None
        return _FakeMem(
            argument_size_in_bytes=self._arg,
            output_size_in_bytes=self._out,
            temp_size_in_bytes=self._temp,
            generated_code_size_in_bytes=7,
            alias_size_in_bytes=0,
        )


class _FakeDevice:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


# ------------------------------------------------ analysis degradation
def test_compiled_entry_full_and_degraded(capsys):
    """Backends that can't report degrade to ABSENT keys + one
    once-per-process note, never a crash, never invented numbers."""
    e = device_mod.compiled_entry("decode", _FakeCompiled(), compile_s=1.5)
    assert e["name"] == "decode" and e["compile_s"] == 1.5
    assert e["flops"] == 1e9 and e["bytes_accessed"] == 2e9
    assert e["argument_bytes"] == 100 and e["temp_bytes"] == 30
    assert e["generated_code_bytes"] == 7
    # Raising cost analysis + None memory analysis: keys absent.
    bad = device_mod.compiled_entry(
        "x", _FakeCompiled(cost_raises=True, mem_returns_none=True)
    )
    assert "flops" not in bad and "temp_bytes" not in bad
    assert bad["name"] == "x"
    # Raising memory analysis on a THIRD program: the note printed once
    # per failure class, not once per program.
    device_mod.compiled_entry("y", _FakeCompiled(cost_raises=True,
                                                 mem_raises=True))
    device_mod.compiled_entry("z", _FakeCompiled(cost_raises=True,
                                                 mem_raises=True))
    out = capsys.readouterr().out
    assert out.count("cost_analysis unavailable") == 1
    assert out.count("memory_analysis() returned None") == 1
    assert out.count("memory_analysis unavailable") == 1


def test_hbm_snapshot_injected_devices():
    """max-used / max-peak / min-limit over the devices that report;
    None-returning and raising devices are skipped; all-silent → None."""
    devs = [
        _FakeDevice({"bytes_in_use": 100, "peak_bytes_in_use": 150,
                     "bytes_limit": 1000}),
        _FakeDevice({"bytes_in_use": 300, "peak_bytes_in_use": 120,
                     "bytes_limit": 900}),
        _FakeDevice(None),                      # CPU-style
        _FakeDevice(RuntimeError("no stats")),  # raising backend
    ]
    snap = device_mod.hbm_snapshot(devs)
    assert snap == {"devices": 2, "used": 300, "peak": 150, "limit": 900}
    assert device_mod.hbm_snapshot([_FakeDevice(None)]) is None
    assert device_mod.hbm_snapshot(
        [_FakeDevice(RuntimeError("x"))]
    ) is None
    # Partial stats dicts yield partial keys, not crashes.
    snap = device_mod.hbm_snapshot([_FakeDevice({"bytes_in_use": 5})])
    assert snap == {"devices": 1, "used": 5}


def test_maybe_emit_hbm_self_disables_and_emits(tmp_path, capsys):
    """First poll on a backend without memory_stats disables the poller
    (one printed note); a reporting backend emits the three gauges and
    feeds the process ledger → /metrics tpuflow_hbm_* rows."""
    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    # Off-TPU shape: nothing reports → self-disable, keys absent.
    assert device_mod.maybe_emit_hbm(
        force=True, devices=[_FakeDevice(None)]
    ) is None
    assert device_mod._POLL_OFF
    assert device_mod.maybe_emit_hbm() is None  # one bool check now
    assert "HBM gauges disabled" in capsys.readouterr().out
    # Re-armed with a reporting device: gauges + ledger + /metrics.
    device_mod._reset_for_tests()
    led = ProcessLedger()
    import tpuflow.obs.goodput as goodput_mod

    old = goodput_mod._LEDGER
    goodput_mod._LEDGER = led
    try:
        snap = device_mod.maybe_emit_hbm(
            force=True,
            devices=[_FakeDevice({"bytes_in_use": 600,
                                  "peak_bytes_in_use": 800,
                                  "bytes_limit": 1000})],
        )
        assert snap["used"] == 600
        # Throttled: an immediate second call inside the poll interval
        # is a no-op (TPUFLOW_DEVICE_POLL_S default 10s).
        assert device_mod.maybe_emit_hbm(
            devices=[_FakeDevice({"bytes_in_use": 1})]
        ) is None
    finally:
        snapshot = led.snapshot()
        goodput_mod._LEDGER = old
    obs.flush()
    gauges = {
        e["name"]: e["value"] for e in _events(d) if e["kind"] == "gauge"
    }
    assert gauges["device.hbm_used"] == 600
    assert gauges["device.hbm_peak"] == 800
    assert gauges["device.hbm_limit"] == 1000
    assert snapshot["hbm_used_bytes"] == 600
    assert snapshot["hbm_used_frac"] == pytest.approx(0.6)
    assert snapshot["hbm_peak_frac"] == pytest.approx(0.8)
    text = prometheus_text(snapshot)
    assert "tpuflow_hbm_used_bytes 600" in text
    assert "tpuflow_hbm_limit_bytes 1000" in text
    assert "tpuflow_hbm_peak_frac 0.8" in text
    # A ledger nobody fed omits the keys entirely (absent, never 0).
    empty = ProcessLedger().snapshot()
    assert "hbm_used_bytes" not in empty
    assert "tpuflow_hbm" not in prometheus_text(empty)


# ------------------------------------------------------ program ledger
def test_program_ledger_merge_budget_and_events(tmp_path, capsys):
    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    path = str(tmp_path / "programs.json")
    led = device_mod.ProgramLedger(source="warmup")
    # Warmup-side entry: compile wall only.
    led.note_entry({"name": "decode", "compile_s": 1.25})
    assert led.write(path) == path
    # AOT-side enrichment of the SAME name merges, not duplicates.
    led2 = device_mod.ProgramLedger(source="serve")
    led2.note_compiled(
        "decode", _FakeCompiled(arg=400, temp=200), compile_s=0.5
    )
    led2.note_compiled("insert", _FakeCompiled(arg=100, temp=0))
    verdict = led2.budget_check(bytes_limit=750)
    assert verdict["resident_bytes"] == 400 + 200 + 100 + 0
    assert verdict["over"] is True  # 700/750 = 93% > the 90% threshold
    led2.write(path)
    with open(path) as f:
        rec = json.load(f)
    by_name = {e["name"]: e for e in rec["programs"]}
    assert set(by_name) == {"decode", "insert"}
    # Merge kept the warmup compile_s? No — the AOT entry's own 0.5
    # wins (later writer), but the warmup-only key survives nothing
    # here; what matters: one entry per name, enriched with analysis.
    assert by_name["decode"]["temp_bytes"] == 200
    assert by_name["decode"]["flops"] == 1e9
    assert rec["budget"]["resident_bytes"] == 700
    obs.flush()
    evs = _events(d)
    progs = [e for e in evs if e["name"] == "device.program"]
    assert {e["program"] for e in progs} == {"decode", "insert"}
    budgets = [e for e in evs if e["name"] == "device.hbm_budget"]
    assert budgets and budgets[-1]["resident_bytes"] == 700


def test_budget_check_thresholds_and_absent_limit(capsys):
    led = device_mod.ProgramLedger()
    led.note_entry({"name": "a", "temp_bytes": 50, "argument_bytes": 30})
    # Under the warn threshold: over=False, no warning printed.
    v = led.budget_check(bytes_limit=1000)
    assert v["over"] is False and v["resident_frac"] == pytest.approx(0.08)
    assert "OOM" not in capsys.readouterr().out
    # Over the threshold: over=True + a printed early warning.
    v = led.budget_check(bytes_limit=85)
    assert v["over"] is True
    assert "expect allocation pressure or OOM" in capsys.readouterr().out
    # No limit resolvable (off-TPU): resident bytes only, ratio keys
    # ABSENT — never invented.
    v = led.budget_check(devices=[_FakeDevice(None)])
    assert v["resident_bytes"] == 80
    assert "resident_frac" not in v and "over" not in v


def test_note_jit_program_gates_and_records(tmp_path, monkeypatch):
    """The compile-fence path: obs off → None; TPUFLOW_DEVICE_LEDGER=0
    → None; armed → a trace-only cost entry in programs.json."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2 + 1)
    assert device_mod.note_jit_program(
        "train.step", f, (jnp.ones((4, 4)),)
    ) is None  # telemetry off
    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    monkeypatch.setenv("TPUFLOW_DEVICE_LEDGER", "0")
    assert device_mod.note_jit_program(
        "train.step", f, (jnp.ones((4, 4)),)
    ) is None
    monkeypatch.delenv("TPUFLOW_DEVICE_LEDGER")
    entry = device_mod.note_jit_program(
        "train.step", f, (jnp.ones((4, 4)),), compile_s=2.5
    )
    assert entry["compile_s"] == 2.5
    assert entry["flops"] > 0  # Lowered.cost_analysis on CPU reports
    with open(os.path.join(d, "programs.json")) as fh:
        rec = json.load(fh)
    assert rec["programs"][0]["name"] == "train.step"


# ---------------------------------------------------- capture governor
class _FakeTracer:
    def __init__(self, start_raises=False):
        self.started = []
        self.stops = 0
        self.dumps = []
        self._start_raises = start_raises

    def start(self, out_dir):
        if self._start_raises:
            raise RuntimeError("profiler unavailable")
        os.makedirs(out_dir, exist_ok=True)
        self.started.append(out_dir)

    def stop(self):
        self.stops += 1

    def memdump(self, path):
        self.dumps.append(path)


def _capturer(tmp_path, clock, tracer=None, **cfg_kw):
    cfg = profcap_mod.CaptureConfig(
        z_mads=4.0, cooldown_s=10.0, max_captures=2, trace_steps=2,
        window=16, warmup=4, **cfg_kw,
    )
    return profcap_mod.AnomalyCapturer(
        str(tmp_path / "profile"), cfg,
        tracer=tracer if tracer is not None else _FakeTracer(),
        clock=clock,
    )


def test_capture_governor_one_cooldown_cap(tmp_path):
    """The acceptance governor: an injected slow-step stream triggers
    exactly ONE bounded capture; a spike inside the cooldown is
    suppressed; past the cooldown a second capture fires; the per-run
    cap suppresses every later trigger."""
    now = [100.0]
    cap = _capturer(tmp_path, lambda: now[0])
    tracer = cap._tracer
    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    for _ in range(8):
        cap.observe_step(0.1)
    assert cap.captures == 0  # steady stream never triggers
    cap.observe_step(5.0)  # spike → capture 1 starts
    assert cap.captures == 1 and len(tracer.started) == 1
    assert "step_time" in tracer.started[0]
    # Bounded: the NEXT trace_steps observations end the trace (no
    # re-judging while live — an anomalous window must not re-trigger
    # against itself).
    cap.observe_step(5.0)
    assert tracer.stops == 0
    cap.observe_step(5.0)
    assert tracer.stops == 1 and len(tracer.dumps) == 1
    # Inside the cooldown: suppressed, counted.
    cap.observe_step(7.0)
    assert cap.captures == 1 and cap.suppressed == 1
    # Past the cooldown: capture 2.
    now[0] += 11.0
    cap.observe_step(7.0)
    assert cap.captures == 2
    cap.observe_step(0.1)
    cap.observe_step(0.1)  # finish capture 2
    assert tracer.stops == 2
    # Past the cooldown again, but the per-run cap (2) suppresses.
    now[0] += 11.0
    cap.observe_step(9.0)
    assert cap.captures == 2 and cap.suppressed == 2
    obs.flush()
    evs = [e for e in _events(d) if e["name"] == "prof.capture"]
    assert len(evs) == 2
    assert evs[0]["reason"] == "step_time"
    assert evs[0]["dir"] == tracer.started[0]
    assert evs[0]["memory_profile"] == tracer.dumps[0]


def test_capture_direct_triggers_and_itl_detector(tmp_path):
    now = [0.0]
    cap = _capturer(tmp_path, lambda: now[0])
    tracer = cap._tracer
    # SLO breach: immediate trigger, no warmup needed.
    cap.note_slo_breach("ttft")
    assert cap.captures == 1 and "slo_ttft" in tracer.started[0]
    cap.observe_itl(0.01)
    cap.observe_itl(0.01)  # bounds the live capture
    assert tracer.stops == 1
    # ITL spike detector past the cooldown.
    now[0] += 11.0
    for _ in range(6):
        cap.observe_itl(0.005)
    cap.observe_itl(1.0)
    assert cap.captures == 2 and "itl" in tracer.started[1]
    # nonfinite while a capture is live: never concurrent.
    cap.note_nonfinite(step=7)
    assert cap.captures == 2
    cap.close()  # end-of-run safety net finishes the live capture
    assert tracer.stops == 2
    assert cap._active is None


def test_capture_wall_deadline_and_broken_tracer(tmp_path, capsys):
    now = [0.0]
    cap = _capturer(tmp_path, lambda: now[0], max_trace_s=5.0)
    tracer = cap._tracer
    cap.note_slo_breach("itl")
    assert cap.captures == 1
    # No observations arrive; the wall deadline ends it via poll().
    now[0] += 6.0
    cap.poll()
    assert tracer.stops == 1
    # A tracer that cannot start disables capture for the run — the
    # trigger path must never become a crash loop.
    bad = _capturer(tmp_path, lambda: now[0],
                    tracer=_FakeTracer(start_raises=True))
    assert bad.trigger("step_time") is False
    assert bad._broken and bad.captures == 0
    assert "capture disabled for this run" in capsys.readouterr().out
    assert bad.trigger("step_time") is False  # no retry, no second note


def test_maybe_from_env_gating(tmp_path, monkeypatch):
    """Disarmed by default → None (the one-check hot path); armed but
    no output dir → None with a note; armed + TPUFLOW_PROF_DIR → live."""
    assert profcap_mod.maybe_from_env() is None
    profcap_mod._reset_for_tests()
    monkeypatch.setenv("TPUFLOW_PROF_TRIGGER", "1")
    assert profcap_mod.maybe_from_env() is None  # no dir resolvable
    profcap_mod._reset_for_tests()
    monkeypatch.setenv("TPUFLOW_PROF_DIR", str(tmp_path / "prof"))
    cap = profcap_mod.maybe_from_env()
    assert cap is not None
    assert profcap_mod.maybe_from_env() is cap  # process singleton


# ------------------------------------------------- fleet + CLI surfaces
def test_fleet_hbm_headroom_aggregation():
    from tpuflow.obs import fleet

    a = {"hbm_used_frac": 0.5, "hbm_peak_frac": 0.6,
         "serve_queue_depth": 1}
    b = {"hbm_used_frac": 0.9, "hbm_peak_frac": 0.95,
         "serve_queue_depth": 2}
    out = fleet.aggregate([a, b])
    # The TIGHTEST replica is the router's constraint, not the mean.
    assert out["hbm_used_frac_max"] == pytest.approx(0.9)
    assert out["hbm_min_headroom_frac"] == pytest.approx(0.1)
    assert out["hbm_peak_frac_max"] == pytest.approx(0.95)
    line = fleet.format_fleet_line(out)
    assert "hbm=0.90/0.95pk" in line
    row = fleet.format_replica_line(
        {"id": "pod-a", "stale": False, "health": 1.0,
         "health_reasons": [], "hbm_used_frac": 0.9}
    )
    assert "hbm=0.90" in row
    # No replica reporting: keys (and the line segment) absent.
    out = fleet.aggregate([{"serve_queue_depth": 1}])
    assert "hbm_used_frac_max" not in out
    assert "hbm=" not in fleet.format_fleet_line(out)


def test_device_summary_cli(tmp_path, capsys):
    """`python -m tpuflow.obs device-summary <run_dir>`: the ledger,
    HBM gauges, budget verdict, and captures reproduced from the run
    dir's files alone — jax-free, mid-run safe."""
    from tpuflow.obs.__main__ import main as obs_main

    run_dir = str(tmp_path / "run")
    d = os.path.join(run_dir, "obs")
    os.makedirs(d)
    with open(os.path.join(d, "programs.json"), "w") as f:
        json.dump({
            "written_ts": 1.0, "source": "serve",
            "programs": [
                {"name": "decode", "compile_s": 1.2, "flops": 1e9,
                 "argument_bytes": 4 << 20, "output_bytes": 1 << 20,
                 "temp_bytes": 2 << 20},
                {"name": "prefill@16", "compile_s": 0.8},
            ],
            "budget": {"resident_bytes": 6 << 20, "programs": 2,
                       "bytes_limit": 16 << 30,
                       "resident_frac": 0.0004, "over": False},
        }, f)
    with open(os.path.join(d, "events.p00000.jsonl"), "w") as f:
        for name, v in (
            ("device.hbm_used", 6 << 30),
            ("device.hbm_peak", 8 << 30),
            ("device.hbm_limit", 16 << 30),
        ):
            f.write(json.dumps(
                {"kind": "gauge", "name": name, "ts": 1.0, "value": v}
            ) + "\n")
        f.write(json.dumps({
            "kind": "event", "name": "prof.capture", "ts": 2.0,
            "reason": "step_time", "dir": "/tmp/p/capture_01_step_time",
            "capture": 1,
        }) + "\n")
    assert obs_main(["device-summary", run_dir, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert {p["name"] for p in out["programs"]} == {"decode",
                                                    "prefill@16"}
    assert out["hbm"]["hbm_used"] == 6 << 30
    assert out["captures"][0]["reason"] == "step_time"
    assert out["budget"]["over"] is False
    # Human mode prints the table + budget + hbm + capture lines.
    assert obs_main(["device-summary", run_dir]) == 0
    text = capsys.readouterr().out
    assert "programs: 2" in text
    assert "decode" in text and "prefill@16" in text
    assert "budget:" in text and "hbm:" in text
    assert "capture[1]: step_time" in text
    # Empty dir: exit 1 with a message, not a trace.
    assert obs_main(
        ["device-summary", str(tmp_path / "nothing")]
    ) == 1
