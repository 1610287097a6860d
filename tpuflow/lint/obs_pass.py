"""Pass 4 — telemetry-name catalog lint (formerly ``tools/obs_lint.py``).

Every literal span/counter/gauge/histogram/event name emitted under
``tpuflow/`` must be registered — with the same kind — in
``tpuflow.obs.catalog.CATALOG``; dynamic-name emitter calls are errors;
the ISSUE-chain REQUIRED_EMITTERS must all exist; the tier-1 duration
guard rides along.

Promoted in this pass (ISSUE 12 satellite): an **unemitted catalog
entry** — a registered name with no literal emitter anywhere — is now
an ERROR, not a warning. Dead ``serve.*``/``train.*`` names in the
catalog make the runbooks describe telemetry that no longer exists.
``UNEMITTED_GRANDFATHER`` is the explicit exception list; it is EMPTY
and must stay empty — stage a name and its emitter in the same PR (the
recorder's own close-path ``obs.dropped`` record is recognized via its
raw dict literal, which is why the list could be burned down to
nothing).

Rules: ``obs-unregistered``, ``obs-kind-mismatch``, ``obs-dynamic-name``,
``obs-missing-required``, ``obs-unemitted``, ``obs-tier1-duration``.
"""

from __future__ import annotations

import json
import os
import re

from tpuflow.lint.core import Sink, Tree

# obs.span("name", ...) / obs.counter("name") / ... (the module-level
# API; `_rec.` covers tpuflow.obs.health, which imports the recorder
# module under that alias to avoid a circular package import)
_API_RE = re.compile(
    r"\b(?:obs|_rec)\.(span|counter|gauge|histogram|event)"
    r"\(\s*[\"']([a-z0-9_.]+)[\"']"
)
# obs.timed_iter(loader, "name") — records histogram observations
_TIMED_ITER_RE = re.compile(
    r"\bobs\.timed_iter\([^)]*?,\s*[\"']([a-z0-9_.]+)[\"']", re.S
)
# rec.record("span", "name", ...) — the low-level recorder API
_RECORD_RE = re.compile(
    r"\.record\(\s*[\"'](span|counter|gauge|histogram|event)[\"']\s*,"
    r"\s*[\"']([a-z0-9_.]+)[\"']",
    re.S,
)
# A raw JSONL record dict with literal kind+name keys — the recorder's
# own close path emits obs.dropped this way (the buffered emitter API
# cannot run while the recorder is closing). Counting it keeps the
# unemitted-entry rule honest without a grandfather entry.
_RAW_RECORD_RE = re.compile(
    r"[\"']kind[\"']\s*:\s*[\"'](span|counter|gauge|histogram|event)"
    r"[\"']\s*,\s*[\"']name[\"']\s*:\s*[\"']([a-z0-9_.]+)[\"']",
    re.S,
)
# An emitter whose NAME is not a string literal is invisible to this
# lint — flag it; emit literal names (one call per name) instead.
_DYNAMIC_RE = re.compile(
    r"\b(?:obs|_rec)\.(span|counter|gauge|histogram|event)\(\s*(?![\"'])\S"
)
# recorder.py's internals forward (kind, self._name) — dynamic by
# construction; its literal names (the raw close-path record) still
# count as emitters above.
_DYNAMIC_EXEMPT = ("tpuflow/obs/recorder.py",)
# The lint package documents the emitter API shapes it greps for; its
# own pattern examples are not emitters.
_SCAN_EXEMPT_PREFIX = "tpuflow/lint/"

# (kind, name) pairs the tree is REQUIRED to emit somewhere — the
# runbook evidence trails of ISSUEs 5-11. The pytest twin
# (tests/test_obs.py) checks these plus its own per-subsystem list.
REQUIRED_EMITTERS: tuple[tuple[str, str], ...] = (
    ("event", "ckpt.io_retry"),
    ("event", "ckpt.io_error"),
    ("event", "ckpt.save_failed"),
    ("event", "ckpt.gc"),
    ("span", "ckpt.upload"),
    ("event", "ckpt.restore_tier"),
    ("event", "ckpt.emergency_save"),
    ("event", "ckpt.verify"),
    ("event", "ckpt.corrupt"),
    ("gauge", "goodput.productive_s"),
    ("gauge", "goodput.lost_s"),
    ("gauge", "goodput.fraction"),
    ("event", "obs.flight"),
    ("event", "obs.export"),
    ("span", "flow.gang_resize"),
    ("event", "flow.member_lost"),
    ("gauge", "dist.mesh_generation"),
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
    ("gauge", "serve.pages_free"),
    ("gauge", "serve.prefix_hits"),
    ("gauge", "serve.spec_accept_rate"),
    ("event", "serve.page_evict"),
    ("counter", "serve.quant_requests"),
    # Serving observatory (ISSUE 13): lifecycle traces, engine-time
    # ledger fractions, and declared-SLO accounting.
    # Spans on the profiler's clock (ISSUE 26): the engine step's
    # phases, the per-request interval, and where set-up goes.
    ("span", "serve.step"),
    ("span", "serve.insert"),
    ("span", "serve.decode.dispatch"),
    ("span", "serve.decode.fence"),
    ("span", "serve.decode.merge"),
    ("span", "serve.harvest"),
    ("event", "serve.first_token"),
    ("span", "data.wait"),
    ("span", "compile"),
    ("span", "state.init"),
    # Set-up on the program's clock (ISSUE 39).
    ("span", "trace"),
    ("span", "lower"),
    ("span", "serve.init"),
    ("span", "serve.warmup.program"),
    ("event", "obs.start"),
    ("event", "serve.trace"),
    ("event", "serve.slo_violation"),
    ("counter", "serve.slo_violations"),
    ("gauge", "serve.idle_fraction"),
    ("gauge", "serve.decode_fraction"),
    ("gauge", "serve.prefill_fraction"),
    ("gauge", "serve.decode_utilization"),
    ("gauge", "serve.decode_read_fraction"),
    ("gauge", "serve.tokens_per_pass"),
    ("gauge", "serve.pool_pad_fraction"),
    ("gauge", "serve.slot_state_bytes"),
    ("gauge", "serve.state_step_inplace"),
    ("gauge", "serve.masked_row_waste"),
    # Disaggregated prefill/decode + tiered KV (ISSUE 19): the ship /
    # import spans, the tier spill/hit/promote trail, and the per-tier
    # page gauges.
    ("span", "serve.kv_ship"),
    ("span", "serve.kv_import"),
    ("event", "serve.tier_hit"),
    ("event", "serve.tier_promote"),
    ("event", "serve.tier_spill"),
    ("gauge", "serve.pages_host"),
    ("gauge", "serve.pages_disk"),
    ("event", "router.ship"),
    ("event", "router.ship_fallback"),
    # Fleet observatory (ISSUE 14): registration, the poll sweep, and
    # the staleness evidence trail.
    ("event", "fleet.register"),
    ("span", "fleet.poll"),
    ("gauge", "fleet.size"),
    ("gauge", "fleet.qps"),
    ("event", "fleet.replica_stale"),
    # Device observatory (ISSUE 15): the per-program ledger, the HBM
    # gauges, the static budget check, and triggered capture.
    ("event", "device.program"),
    ("gauge", "device.hbm_used"),
    ("gauge", "device.hbm_peak"),
    ("gauge", "device.hbm_limit"),
    ("event", "device.hbm_budget"),
    ("event", "prof.capture"),
    # Decision observatory (ISSUE 16): the run registry's append audit
    # and the alert engine's deduplicated lifecycle events.
    ("event", "registry.append"),
    ("event", "alert.fired"),
    ("event", "alert.resolved"),
    # Front-door router (ISSUE 17): admission, failover, and drain
    # evidence — the chaos harness's zero-drop claim is audited from
    # exactly these events.
    ("event", "router.admit"),
    ("event", "router.reject"),
    ("event", "router.retry"),
    ("event", "router.reroute"),
    ("event", "router.drain"),
    ("event", "router.replace"),
    ("gauge", "router.queue_depth"),
    ("gauge", "router.budget_pages"),
    # End-to-end tracing (ISSUE 18): tail-sampling escalation, flush
    # audit, and the appended/dropped span counters.
    ("event", "trace.escalate"),
    ("event", "trace.flush"),
    ("counter", "trace.spans"),
    ("counter", "trace.dropped"),
    ("event", "quant.decision"),
    ("event", "quant.kernel_fallback"),
    ("event", "ops.flash_bwd_fused"),
    ("event", "train.remat_policy"),
    ("gauge", "train.exposed_comm_s"),
    ("gauge", "train.comm_overlap_s"),
)

# Catalog entries allowed to have no emitter. EMPTY by design: the
# unemitted warning was promoted to an error (ISSUE 12) and the list
# burned down — register a name in the same PR as its emitter. Add an
# entry here only with a comment saying which PR removes it.
UNEMITTED_GRANDFATHER: frozenset[str] = frozenset()

# Tier-1 duration guard (ISSUE 6 satellite): tests/conftest.py records
# every full 'not slow' session's wall time; exceeding the guard fails
# the lint BEFORE CI starts getting killed by the hard timeout.
# ISSUE 16 slow-mark audit: the suite had crept to ~1170s; marking the
# 14 biggest call-time outliers brought a clean run to 767s, and the
# guard was pinned at 800 so that headroom can't silently erode back.
# ISSUE 18 re-pin: the accumulated fast suites (trace units included,
# all jax-free) sit just over 800 on the CI host; 820 keeps ~50s of
# real headroom under the 870 hard budget.
TIER1_BUDGET_S = 870.0
TIER1_GUARD_S = 820.0
TIER1_DURATION_FILE = ".tier1_duration.json"
_TIER1_MIN_TESTS = 100


def tier1_duration_guard(root: str) -> str | None:
    """Error string when the last recorded full tier-1 session exceeded
    the duration guard, else None."""
    try:
        with open(os.path.join(root, TIER1_DURATION_FILE)) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    if rec.get("markexpr") != "not slow":
        return None
    try:
        if int(rec.get("testscollected", 0)) < _TIER1_MIN_TESTS:
            return None
        dur = float(rec.get("duration_s", 0.0))
    except (TypeError, ValueError):
        return None
    if dur > TIER1_GUARD_S:
        return (
            f"tier-1 suite recorded {dur:.0f}s, over the "
            f"{TIER1_GUARD_S:.0f}s guard of the {TIER1_BUDGET_S:.0f}s "
            "budget — slow-mark the newest long tests or speed the "
            "suite up before CI starts timing out"
        )
    return None


def _lineno(src: str, pos: int) -> int:
    return src.count("\n", 0, pos) + 1


def emitted_names(tree: Tree) -> list[tuple[str, str, str, int]]:
    """(relpath, kind, name, lineno) for every literal emitter call
    under tpuflow/."""
    out = []
    for rel in tree.files():
        norm = rel.replace("\\", "/")
        if not norm.startswith("tpuflow/") or norm.startswith(
            _SCAN_EXEMPT_PREFIX
        ):
            continue
        src = tree.source(rel)
        for m in _API_RE.finditer(src):
            out.append((rel, m.group(1), m.group(2), _lineno(src, m.start())))
        for m in _TIMED_ITER_RE.finditer(src):
            out.append((rel, "histogram", m.group(1), _lineno(src, m.start())))
        for m in _RECORD_RE.finditer(src):
            out.append((rel, m.group(1), m.group(2), _lineno(src, m.start())))
        for m in _RAW_RECORD_RE.finditer(src):
            out.append((rel, m.group(1), m.group(2), _lineno(src, m.start())))
    return out


def run(
    tree: Tree,
    catalog: dict | None = None,
    required: tuple = REQUIRED_EMITTERS,
    grandfather: frozenset = UNEMITTED_GRANDFATHER,
    duration_guard: bool = True,
):
    if catalog is None:
        from tpuflow.obs.catalog import CATALOG as catalog

    sink = Sink(tree)
    used: set[str] = set()
    kinds: set[tuple[str, str]] = set()
    for rel, kind, name, lineno in emitted_names(tree):
        used.add(name)
        kinds.add((kind, name))
        if name not in catalog:
            sink.emit(
                rel, lineno, "obs-unregistered",
                f"emits {kind} {name!r} not registered in "
                "tpuflow.obs.catalog.CATALOG",
            )
        elif catalog[name][0] != kind:
            sink.emit(
                rel, lineno, "obs-kind-mismatch",
                f"emits {name!r} as {kind} but the catalog registers "
                f"it as {catalog[name][0]}",
            )
    for rel in tree.files():
        norm = rel.replace("\\", "/")
        if (
            not norm.startswith("tpuflow/")
            or norm in _DYNAMIC_EXEMPT
            or norm.startswith(_SCAN_EXEMPT_PREFIX)
        ):
            continue
        src = tree.source(rel)
        for m in _DYNAMIC_RE.finditer(src):
            sink.emit(
                rel, _lineno(src, m.start()), "obs-dynamic-name",
                f"emitter with a non-literal name ({m.group(0)!r}...) "
                "is invisible to this lint — emit literal catalog "
                "names instead",
            )
    for kind, name in required:
        if (kind, name) not in kinds:
            sink.emit(
                "tpuflow", 0, "obs-missing-required",
                f"required emitter missing from tpuflow/: {name!r} "
                f"({kind})",
            )
    for name in sorted(set(catalog) - used - set(grandfather)):
        sink.emit(
            "tpuflow/obs/catalog.py", 1, "obs-unemitted",
            f"catalog name {name!r} has no literal emitter in tpuflow/ "
            "— dead catalog entries make runbooks describe telemetry "
            "that does not exist; delete the entry or land its emitter "
            "(UNEMITTED_GRANDFATHER is the explicit, empty-by-design "
            "exception list)",
        )
    if duration_guard:
        err = tier1_duration_guard(tree.root)
        if err:
            sink.emit(TIER1_DURATION_FILE, 0, "obs-tier1-duration", err)
    return sink.result()
