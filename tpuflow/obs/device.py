"""Device observatory (ISSUE 15): the device-side twin of the goodput
ledger — what the chip compiled, what it costs, and what HBM is doing.

The observatories so far (goodput PR 6, serving PR 13, fleet PR 14)
account for host **wall time**; nothing observed the **device**. This
module closes that gap with three host-side pieces (no jitted program
gains an operand — ``compile_stats()`` is identical with everything
armed):

- **Program ledger.** :class:`ProgramLedger` records one entry per
  compiled XLA program — compile wall-s, ``cost_analysis()`` FLOPs /
  bytes-accessed, ``memory_analysis()`` argument/output/temp/
  generated-code bytes — into a ``programs.json`` run artifact (merged
  by program name across writers: warmup fences, the train compile
  fence, the AOT prewarm tool) plus a ``device.program`` event per
  entry. Backends that can't report (CPU ``memory_stats()`` is None;
  some backends raise from the analyses) degrade to **absent keys**
  with a once-per-process note — never a crash, never invented numbers
  (the Orbax artifact discipline: the ledger is the machine-readable
  handoff interface for compiled programs).

- **HBM gauges.** :func:`maybe_emit_hbm` polls ``device.memory_stats()``
  at the fences ``StepClock``/``ServeEngine`` already pay, throttled by
  ``TPUFLOW_DEVICE_POLL_S`` — ``device.hbm_used`` / ``device.hbm_peak``
  / ``device.hbm_limit`` gauges in the event stream and the live
  ``/metrics`` + ``/status`` exporter (``tpuflow_hbm_*``). Off-TPU the
  poller disables itself after the first probe; every later call is one
  module-bool check.

- **Static HBM budget check.** :meth:`ProgramLedger.budget_check` sums
  resident program temp+argument bytes against
  ``memory_stats()['bytes_limit']`` and records a ``device.hbm_budget``
  event — warning *before* an OOM, at warmup/prewarm time, not at step
  3000. The sum double-counts arguments shared between programs
  (params), which keeps the check conservative: it can only warn early.

Consumers: ``python -m tpuflow.obs device-summary <run_dir>`` (jax-free
— jax is only imported inside the polling/lowering helpers), the
timeline card's Device section, ``tpu_watch --follow/--fleet`` HBM
segments, and bench legs persisting the ledger beside their records.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Iterable

from tpuflow.obs import recorder as _rec
from tpuflow.utils import knobs

PROGRAMS_NAME = "programs.json"

# Budget-check warn threshold: resident program bytes above this
# fraction of bytes_limit are flagged. Below 1.0 on purpose — runtime
# allocations (activations in flight, collectives scratch) ride on top
# of the static program footprint, so "fits exactly" already means OOM.
BUDGET_WARN_FRAC = 0.9

_WARNED: set[str] = set()


def _warn_once(key: str, msg: str) -> None:
    """Graceful-degradation notes print once per process: an off-TPU
    backend answering None for every program must not spam one line per
    ledger entry."""
    if key not in _WARNED:
        _WARNED.add(key)
        print(f"[tpuflow] {msg}")


# --------------------------------------------- compiled-program analysis
def cost_analysis_dict(compiled_or_lowered) -> dict[str, float]:
    """``cost_analysis()`` → ``{flops, bytes_accessed}``. On the installed
    jax both ``Compiled`` and ``Lowered`` return a plain dict, on the CPU
    and on the TPU (chip_smoke.py prints the type). A backend that raises
    yields absent keys, never a crash."""
    out: dict[str, float] = {}
    try:
        ca = compiled_or_lowered.cost_analysis()
    except Exception as e:
        _warn_once(
            "cost_analysis",
            "device ledger: cost_analysis unavailable on this backend "
            f"({e!r}); recording absent keys",
        )
        return out
    flops = ca.get("flops")
    if isinstance(flops, (int, float)):
        out["flops"] = float(flops)
    accessed = ca.get("bytes accessed")
    if isinstance(accessed, (int, float)):
        out["bytes_accessed"] = float(accessed)
    return out


_MEM_ATTRS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)


def memory_analysis_dict(compiled) -> dict[str, int]:
    """``memory_analysis()`` → byte counts by role; ``None`` returns and
    raising backends degrade to absent keys with a once-per-process
    note."""
    out: dict[str, int] = {}
    try:
        ma = compiled.memory_analysis()
    except Exception as e:
        _warn_once(
            "memory_analysis",
            "device ledger: memory_analysis unavailable on this backend "
            f"({e!r}); recording absent keys",
        )
        return out
    if ma is None:
        _warn_once(
            "memory_analysis_none",
            "device ledger: memory_analysis() returned None on this "
            "backend; recording absent keys",
        )
        return out
    for attr, key in _MEM_ATTRS:
        v = getattr(ma, attr, None)
        if isinstance(v, (int, float)):
            out[key] = int(v)
    return out


def compiled_entry(
    name: str,
    compiled,
    *,
    compile_s: float | None = None,
    source: str | None = None,
) -> dict[str, Any]:
    """One ledger entry from an AOT-compiled program (``jit(...)
    .lower(...).compile()`` — the only object that carries BOTH
    analyses)."""
    entry: dict[str, Any] = {"name": str(name)}
    if compile_s is not None:
        entry["compile_s"] = round(float(compile_s), 4)
    if source:
        entry["source"] = source
    entry.update(cost_analysis_dict(compiled))
    entry.update(memory_analysis_dict(compiled))
    return entry


# -------------------------------------------------------- program ledger
class ProgramLedger:
    """Per-run ledger of compiled-program footprints.

    Entries merge by program NAME — the warmup fence records compile
    wall-s, the AOT path later enriches the same name with cost/memory
    analysis, and ``write()`` merges with whatever an earlier writer
    already persisted, so one ``programs.json`` accumulates the run's
    whole compiled inventory."""

    def __init__(self, source: str = "run"):
        self.source = source
        self._by_name: dict[str, dict] = {}
        self.budget: dict[str, Any] | None = None

    @property
    def programs(self) -> list[dict]:
        return list(self._by_name.values())

    def note_entry(self, entry: dict) -> dict:
        """Record (or enrich) one entry and emit its ``device.program``
        event. The event renames ``name`` → ``program`` (the recorder
        schema already uses ``name`` for the catalog name)."""
        name = str(entry.get("name", "?"))
        merged = self._by_name.setdefault(name, {"name": name})
        merged.update({k: v for k, v in entry.items() if v is not None})
        merged.setdefault("source", self.source)
        attrs = {k: v for k, v in merged.items() if k != "name"}
        _rec.event("device.program", program=name, **attrs)
        return merged

    def note_compiled(
        self, name: str, compiled, *, compile_s: float | None = None
    ) -> dict:
        return self.note_entry(
            compiled_entry(
                name, compiled, compile_s=compile_s, source=self.source
            )
        )

    # ------------------------------------------------------ budget check
    def resident_bytes(self) -> int:
        """Static residency claim of the recorded inventory: temp +
        argument bytes summed over programs. Arguments shared between
        programs (params pytrees) double-count — deliberately: the check
        is an early-warning upper bound, and a conservative bound can
        only warn early, never miss an OOM it could have seen."""
        total = 0
        for e in self._by_name.values():
            total += int(e.get("temp_bytes", 0)) + int(
                e.get("argument_bytes", 0)
            )
        return total

    def budget_check(
        self, bytes_limit: int | None = None, *, devices=None
    ) -> dict[str, Any]:
        """Static HBM budget verdict, recorded as a ``device.hbm_budget``
        event. ``bytes_limit`` defaults from ``memory_stats()`` (absent
        off-TPU → the verdict carries resident bytes only, no ratio —
        keys absent, never invented)."""
        if bytes_limit is None:
            snap = hbm_snapshot(devices)
            if snap is not None:
                bytes_limit = snap.get("limit")
        resident = self.resident_bytes()
        verdict: dict[str, Any] = {
            "resident_bytes": resident,
            "programs": len(self._by_name),
        }
        if bytes_limit:
            frac = resident / float(bytes_limit)
            verdict["bytes_limit"] = int(bytes_limit)
            verdict["resident_frac"] = round(frac, 4)
            verdict["over"] = frac > BUDGET_WARN_FRAC
            if verdict["over"]:
                print(
                    "[tpuflow] device ledger: static program residency "
                    f"{resident / 2**30:.2f} GiB is {100.0 * frac:.0f}% "
                    f"of the {bytes_limit / 2**30:.2f} GiB HBM limit — "
                    "expect allocation pressure or OOM "
                    "(README: Device observatory runbook)"
                )
        _rec.event("device.hbm_budget", **verdict)
        self.budget = verdict
        return verdict

    # ------------------------------------------------------------- write
    def write(self, path: str | None = None) -> str | None:
        """Persist (merge-by-name with any existing file) the ledger as
        ``programs.json``. Default location: beside the recorder's event
        fragments (``<obs_dir>/programs.json``); with telemetry disabled
        and no explicit path, a no-op returning None. Atomic tmp+rename
        so a concurrent reader never sees a torn artifact."""
        if path is None:
            rec = _rec.recorder()
            if rec is None:
                return None
            path = os.path.join(rec.directory, PROGRAMS_NAME)
        existing: dict[str, dict] = {}
        budget = self.budget
        try:
            with open(path) as f:
                prior = json.load(f)
            for e in prior.get("programs", []):
                if isinstance(e, dict) and e.get("name"):
                    existing[str(e["name"])] = e
            if budget is None and isinstance(prior.get("budget"), dict):
                budget = prior["budget"]
        except (OSError, ValueError):
            pass
        for name, e in self._by_name.items():
            merged = existing.setdefault(name, {"name": name})
            merged.update({k: v for k, v in e.items() if v is not None})
        record: dict[str, Any] = {
            "written_ts": time.time(),
            "source": self.source,
            "programs": sorted(
                existing.values(), key=lambda e: e.get("name", "")
            ),
        }
        if budget is not None:
            record["budget"] = budget
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(record, f, indent=1)
            os.replace(tmp, path)
        except OSError as e:
            # The ledger is evidence, never a failure mode.
            print(f"[tpuflow] device ledger write failed (ignored): {e}")
            return None
        return path


def note_jit_program(
    name: str,
    jit_fn,
    args: tuple,
    *,
    compile_s: float | None = None,
    source: str = "train",
) -> dict | None:
    """Record an already-jitted function at its compile fence.

    Re-lowering an executed jit fn is TRACE-only (no XLA backend
    compile), so this collects ``Lowered.cost_analysis()`` cheaply;
    ``memory_analysis`` needs the compiled executable and is only
    recorded on the AOT paths (``ServeEngine.aot_lower`` /
    ``tools/prewarm_cache.py``) — absent keys here, by design. Gated on
    telemetry + ``TPUFLOW_DEVICE_LEDGER``; never raises into the loop."""
    if not _rec.enabled():
        return None
    if not knobs.get_bool("TPUFLOW_DEVICE_LEDGER"):
        return None
    entry: dict[str, Any] = {"name": str(name), "source": source}
    if compile_s is not None:
        entry["compile_s"] = round(float(compile_s), 4)
    try:
        lowered = jit_fn.lower(*args)
        entry.update(cost_analysis_dict(lowered))
    except Exception as e:
        _warn_once(
            f"lower:{name}",
            f"device ledger: re-lowering {name!r} for cost analysis "
            f"failed ({e!r}); recording compile time only",
        )
    ledger = ProgramLedger(source=source)
    ledger.note_entry(entry)
    ledger.write()
    return entry


# ------------------------------------------------------------ HBM gauges
def hbm_snapshot(devices=None) -> dict[str, Any] | None:
    """One ``memory_stats()`` sweep over ``devices`` (default
    ``jax.local_devices()``; tests inject fakes). Returns ``{devices,
    used, peak, limit}`` — ``used``/``peak`` are the max over devices
    and ``limit`` the min (the binding device is the one that OOMs
    first), each key present only when at least one device reported it.
    ``None`` when no device answers (CPU backends return None) — absent
    keys, never invented."""
    if devices is None:
        try:
            import jax

            devices = jax.local_devices()
        except Exception:
            return None
    used = peak = limit = None
    n = 0
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        n += 1
        u = stats.get("bytes_in_use")
        if isinstance(u, (int, float)):
            used = int(u) if used is None else max(used, int(u))
        p = stats.get("peak_bytes_in_use")
        if isinstance(p, (int, float)):
            peak = int(p) if peak is None else max(peak, int(p))
        lim = stats.get("bytes_limit")
        if isinstance(lim, (int, float)):
            limit = int(lim) if limit is None else min(limit, int(lim))
    if n == 0:
        return None
    out: dict[str, Any] = {"devices": n}
    if used is not None:
        out["used"] = used
    if peak is not None:
        out["peak"] = peak
    if limit is not None:
        out["limit"] = limit
    return out


def emit_hbm(snap: dict) -> None:
    """Record one HBM snapshot as gauges + the live process-ledger feed
    (the /metrics ``tpuflow_hbm_*`` rows)."""
    used = snap.get("used")
    peak = snap.get("peak")
    limit = snap.get("limit")
    if used is not None:
        _rec.gauge("device.hbm_used", used)
    if peak is not None:
        _rec.gauge("device.hbm_peak", peak)
    if limit is not None:
        _rec.gauge("device.hbm_limit", limit)
    from tpuflow.obs import goodput as _goodput

    _goodput.live().note_device_hbm(used, peak, limit)


# Poller state: one monotonic compare when the interval hasn't elapsed,
# one bool check forever after the first probe on a backend without
# memory_stats — the fences that call this are the hot loop's.
_POLL_NEXT = 0.0
_POLL_OFF = False


def maybe_emit_hbm(force: bool = False, devices=None) -> dict | None:
    """Throttled HBM poll for the StepClock / ServeEngine fences
    (``TPUFLOW_DEVICE_POLL_S``; 0 disables). Self-disables after the
    first probe on a backend where ``memory_stats()`` is unavailable."""
    global _POLL_NEXT, _POLL_OFF
    if _POLL_OFF and not force:
        return None
    now = time.monotonic()
    if not force and now < _POLL_NEXT:
        return None
    interval = knobs.get_float_lenient("TPUFLOW_DEVICE_POLL_S")
    if interval <= 0 and not force:
        _POLL_OFF = True
        return None
    _POLL_NEXT = now + max(float(interval), 0.0)
    snap = hbm_snapshot(devices)
    if snap is None:
        _POLL_OFF = True
        _warn_once(
            "hbm_off",
            "device observatory: memory_stats() unavailable on this "
            "backend; HBM gauges disabled (keys absent, never invented)",
        )
        return None
    _POLL_OFF = False
    emit_hbm(snap)
    return snap


def _reset_for_tests() -> None:
    global _POLL_NEXT, _POLL_OFF
    _POLL_NEXT = 0.0
    _POLL_OFF = False
    _WARNED.clear()


# ------------------------------------------------------ jax-free reading
def load_programs(run_dir: str) -> dict | None:
    """The run's ``programs.json`` (``<run_dir>/obs/`` or the run root),
    or None. Pure file reading — safe from a login shell mid-run."""
    from tpuflow.obs.timeline import OBS_SUBDIR

    for candidate in (
        os.path.join(run_dir, OBS_SUBDIR, PROGRAMS_NAME),
        os.path.join(run_dir, PROGRAMS_NAME),
    ):
        try:
            with open(candidate) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(rec, dict) and isinstance(rec.get("programs"), list):
            rec["path"] = candidate
            return rec
    return None


def device_summary(run_dir: str) -> dict[str, Any]:
    """Fold the run's device evidence — ``programs.json``, the last
    ``device.hbm_*`` gauges, budget verdicts, and ``prof.capture``
    events — into one dict (the ``device-summary`` CLI's payload).
    jax-free: file reads only."""
    from tpuflow.obs.timeline import load_run_events

    out: dict[str, Any] = {}
    ledger = load_programs(run_dir)
    if ledger:
        out["programs_path"] = ledger.get("path")
        out["programs"] = ledger["programs"]
        if isinstance(ledger.get("budget"), dict):
            out["budget"] = ledger["budget"]
    hbm: dict[str, float] = {}
    captures: list[dict] = []
    for ev in load_run_events(run_dir):
        kind, name = ev.get("kind"), ev.get("name", "")
        if kind == "gauge" and name in (
            "device.hbm_used", "device.hbm_peak", "device.hbm_limit"
        ):
            try:
                key = name[len("device."):]
                v = float(ev.get("value", 0.0))
                hbm[key] = v
                if key != "hbm_limit":
                    hbm[f"{key}_max"] = max(hbm.get(f"{key}_max", 0.0), v)
            except (TypeError, ValueError):
                pass
        elif kind == "event" and name == "prof.capture":
            captures.append({
                k: v for k, v in ev.items()
                if k not in ("kind", "name", "pid")
            })
        elif kind == "event" and name == "device.hbm_budget":
            out.setdefault("budget", {
                k: v for k, v in ev.items()
                if k not in ("kind", "name", "ts", "proc", "pid", "launch")
            })
    if hbm:
        out["hbm"] = hbm
    if captures:
        out["captures"] = captures
    return out


def summarize_entry(e: dict) -> str:
    """One human table line for a programs.json entry."""

    def _fmt_bytes(v):
        return "-" if v is None else f"{v / 2**20:9.2f}"

    flops = e.get("flops")
    return (
        f"  {e.get('name', '?'):<16} "
        f"{e.get('compile_s', '-')!s:>9}  "
        f"{'-' if flops is None else f'{flops:.3g}':>10}  "
        f"{_fmt_bytes(e.get('argument_bytes')):>9}  "
        f"{_fmt_bytes(e.get('output_bytes')):>9}  "
        f"{_fmt_bytes(e.get('temp_bytes')):>9}"
    )
