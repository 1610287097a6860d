"""Device time by the program's named scopes, the program's own spans on
the profiler's clock, and span arithmetic over the program's events.

A traced run leaves two records: the profiler's `.xplane.pb` (device
operations, and on the `/host:` plane every `TraceAnnotation`, the
program's spans among them) and the program's event file (spans with ids,
parents, a monotonic start and a `request`). This module reads both, once
per run, for the per-layer readers.

Where the scope path of a device operation lives (found on the chip, PR
26, jax 0.9.0): not on the event and not in its name, but in the `tf_op`
stat of the event's *metadata* (`XEventMetadata.stats`), which
`jax.profiler.ProfileData` does not expose. So the metadata is read from
the file's wire format directly (a few fields of four messages) and joined
to `ProfileData`'s events on the operation's name (on the device lines the
whole HLO instruction) and its program: the metadata's `program_id` is the
number in brackets in the name of the `XLA Modules` event that an
operation's event lies in."""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re

from benchmark.harness import trace as trace_mod

MIN_GAP_S = trace_mod.MIN_GAP_S
# A program span on the host plane: a dotted lower-case name (`serve.step`,
# `data.wait`) that is not the benchmark's own. The runtime's events there
# are CamelCase, or have `::`, `=>` or spaces (and where the CPU runs the
# programs, its operations lie there too: `dot.101`, `while.2`).
PROGRAM_SPAN_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z_][a-z0-9_]*)+$")
TOKEN_RE = re.compile(r"[A-Za-z_][\w.\-]*")

# The train step's and the engine's own scopes. Those inside a model (a
# block's parts, flax's modules around them) are the family's:
# `BLOCK_SCOPES` and `MODULE_SCOPES` of `benchmark/families/<family>.py`.
STEP_SCOPES = ("loss", "optimizer")
ENGINE_SCOPES = ("serve.decode", "serve.prefill", "serve.insert", "serve.verify")
REMAT = "rematted_computation"
ADMISSION_SPANS = ("serve.admit", "serve.prefill", "serve.insert")


# ------------------------------------------------- the file's wire format
def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, the bytes for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, value


# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4, stat_metadata = 5
# (maps: key = 1, value = 2); XEventMetadata: name = 2, stats = 5;
# XStatMetadata: name = 2; XStat: metadata_id = 1, uint64/int64 = 3/4,
# str_value = 5, ref_value = 7 (the string is a stat's name).
def operation_scopes(path: str) -> dict[str, frozenset[str]]:
    """Per device operation, under the key `op_key` makes of its program
    and its name (as `ProfileData` gives it), the tokens of its scope
    path: the `tf_op` stat of its metadata. An operation the compiler
    added (a layout copy, a transfer) has no name stack: it takes what
    every name stack of its program shares, the program's root scopes."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    paths: dict[str, tuple[str | None, int | None]] = {}
    for no, _, plane in fields(space):
        if no != 1:
            continue
        name, metas, stat_names = "", [], {}
        for no2, _, value in fields(plane):
            if no2 == 2:
                name = bytes(value).decode()
            elif no2 == 4:
                metas += [v for n, _, v in fields(value) if n == 2]
            elif no2 == 5:
                key = meta = None
                for n, _, v in fields(value):
                    key, meta = (v, meta) if n == 1 else (key, v)
                stat_names[key] = next(
                    (bytes(v).decode() for n, _, v in fields(meta) if n == 2), ""
                )
        if not trace_mod._is_device_plane(name):
            continue
        ids = {v: k for k, v in stat_names.items()}
        for meta in metas:
            op, scope, program = "", None, None
            for n, _, v in fields(meta):
                if n == 2:
                    op = bytes(v).decode()
                elif n == 5:
                    stat = dict((sn, sv) for sn, _, sv in fields(v))
                    if stat.get(1) == ids.get("program_id"):
                        program = stat.get(3, stat.get(4))
                    elif stat.get(1) == ids.get("tf_op"):
                        scope = (
                            bytes(stat[5]).decode() if 5 in stat else stat_names.get(stat.get(7))
                        )
            paths[op_key(program, op)] = (scope, program)
    return inherit_program_scopes(paths)


def op_key(program, name: str) -> str:
    return f"{program}|{name}"


def inherit_program_scopes(paths: dict) -> dict[str, frozenset[str]]:
    """name -> (scope path or None, program) to name -> tokens. A path
    that is a name stack starts with `jit(`; what all of a program's name
    stacks share are its roots. An operation without a path, or with an
    argument's name for one (`cache['h']['block']['cached_key']`: a copy
    of that argument), takes the roots, the latter with `argument`."""
    roots: dict = {}
    for scope, program in paths.values():
        if scope and scope.startswith("jit("):
            toks = tokens(scope)
            roots[program] = roots[program] & toks if program in roots else toks
    out = {}
    for op, (scope, program) in paths.items():
        if scope and scope.startswith("jit("):
            out[op] = tokens(scope)
        else:
            out[op] = roots.get(program, frozenset()) | ({"argument"} if scope else set())
    return out


# ----------------------------------------------------- the device by scope
def tokens(scope_path: str) -> frozenset[str]:
    """`jit(step)/transpose(jvp(Model))/while/body/checkpoint/h/block/attn_core/mul:`
    -> {jit, step, transpose, jvp, Model, while, ..., attn_core, mul}."""
    return frozenset(TOKEN_RE.findall(scope_path))


def label(toks: frozenset[str], family) -> str:
    """The first of the named scopes, in this order, that an operation
    lies under: a block's parts (the family's), then the step's, then
    flax's modules (the family's), then the engine's programs. Tokens are a
    set, so nesting is not read; the order puts the scopes that lie inside
    before those around them."""
    for group in (family.BLOCK_SCOPES, STEP_SCOPES, family.MODULE_SCOPES, ENGINE_SCOPES):
        for scope in group:
            if scope in toks:
                return scope
    return "unscoped"


def key_by_program(ops: list[tuple], modules: list[tuple]) -> list[tuple]:
    """The operations' events renamed to `op_key(program, name)`, the
    program being the one whose `XLA Modules` event (`jit_step(<id>)`) the
    operation starts in: two programs may hold one instruction text."""
    runs = sorted(
        (s, e, m.group(1)) for s, e, name in modules
        if (m := re.search(r"\((\d+)\)$", name))
    )
    starts = [r[0] for r in runs]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        program = runs[i][2] if i >= 0 and s <= runs[i][1] else None
        out.append((s, e, op_key(program, name)))
    return out


def reduce_planes(planes: list[dict], tokens_of: dict[str, frozenset[str]]) -> dict:
    """On plain data: planes as `trace.reduce_planes` takes them (device
    events under their full names) and each operation's scope tokens."""
    busy, idle = [], []
    ops: dict[str, float] = {}
    host_spans: list[tuple[float, float, str]] = []
    for plane in planes:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                host_spans += [
                    ev for ev in line["events"]
                    if PROGRAM_SPAN_RE.match(ev[2])
                    and not ev[2].startswith(trace_mod.ANNOTATION_PREFIX)
                ]
        elif trace_mod._is_device_plane(plane["name"]):
            for line in plane["lines"]:
                if line["name"] != trace_mod.OPS_LINE:
                    continue
                merged = trace_mod._union([(s, e) for s, e, _ in line["events"]])
                busy.append(sum(e - s for s, e in merged))
                idle.append([
                    (a[1], b[0]) for a, b in zip(merged, merged[1:])
                    if b[0] - a[1] >= MIN_GAP_S
                ])
                for name, t in trace_mod._self_times(line["events"]).items():
                    ops[name] = ops.get(name, 0.0) + t
    n_dev = max(len(busy), 1)
    by_tokens: dict[frozenset[str], float] = {}
    for name, t in ops.items():
        toks = tokens_of.get(name, frozenset())
        by_tokens[toks] = by_tokens.get(toks, 0.0) + t / n_dev
    idle_by_span: dict[str, float] = {}
    for gaps in idle:
        for s, e in gaps:
            mid = 0.5 * (s + e)
            over = [h for h in host_spans if h[0] <= mid <= h[1]]
            owner = min(over, key=lambda h: h[1] - h[0])[2] if over else "none"
            idle_by_span[owner] = idle_by_span.get(owner, 0.0) + (e - s) / n_dev
    return {
        "busy_s": sum(busy) / n_dev if busy else 0.0,
        "by_tokens": by_tokens,
        "host_spans": host_spans,
        "idle_by_span": idle_by_span,
    }


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, _mtime: float, family) -> dict:
    import jax.profiler as jp

    data = jp.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = trace_mod._is_device_plane(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = {
            ln.name: [
                (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                for ev in ln.events
            ]
            for ln in plane.lines
        }
        if device and trace_mod.OPS_LINE in lines:
            lines[trace_mod.OPS_LINE] = key_by_program(
                lines[trace_mod.OPS_LINE], lines.get(trace_mod.MODULES_LINE, [])
            )
        planes.append({
            "name": plane.name,
            "lines": [{"name": k, "events": v} for k, v in lines.items()],
        })
    red = reduce_planes(planes, operation_scopes(path))
    _log_tables(red, family)
    return red


def reduce_file(path: str, family) -> dict:
    """Parsed once per file however many readers ask; the first time, the
    tables go to the log, labelled by the family's scopes."""
    return _reduce_file(path, os.path.getmtime(path), family)


def find_trace(run: dict) -> str | None:
    """The run's `.xplane.pb`, where `runner.Tracer` had it written."""
    from benchmark.harness import runner

    if not (run.get("traced") or {}).get("trace"):
        return None
    files = glob.glob(os.path.join(
        runner.RUN_DIR, f"trace-{run['cell']['name']}", "plugins", "profile", "*", "*.xplane.pb"
    ))
    return files[0] if files else None


def device(run: dict) -> dict | None:
    """The run's device time by scope, or None without a trace or where no
    operation carries a scope path."""
    path = find_trace(run)
    if path is None:
        return None
    red = reduce_file(path, run["cell"]["family"])
    return red if red["busy_s"] and any(red["by_tokens"]) else None


def seconds_under(red: dict, any_of: tuple[str, ...], none_of: tuple[str, ...] = (),
                  all_of: tuple[str, ...] = ()) -> float | None:
    """Device self time of the operations under one of `any_of` (and all of
    `all_of`, and none of `none_of`); None where no operation is under
    `any_of` at all, which is a program without those scopes."""
    found, total = False, 0.0
    for toks, t in red["by_tokens"].items():
        if not toks.intersection(any_of):
            continue
        found = True
        if toks.issuperset(all_of) and not toks.intersection(none_of):
            total += t
    return total if found else None


def share_under(run: dict, any_of, none_of=(), all_of=()) -> float | None:
    """That time as a share (%) of the traced window's device busy time."""
    red = device(run)
    if red is None:
        return None
    seconds = seconds_under(red, tuple(any_of), tuple(none_of), tuple(all_of))
    return None if seconds is None else 100.0 * seconds / red["busy_s"]


def by_label(red: dict, family) -> list[list]:
    out: dict[str, float] = {}
    for toks, t in red["by_tokens"].items():
        key = label(toks, family) + (" (recomputed)" if REMAT in toks else "")
        out[key] = out.get(key, 0.0) + t
    return sorted(([k, v] for k, v in out.items()), key=lambda kv: -kv[1])


def _log_tables(red: dict, family) -> None:
    from benchmark.harness.runner import log

    busy = red["busy_s"]
    if not busy:
        return
    rows = [f"{k} {100 * v / busy:.2f}%" for k, v in by_label(red, family)[:10]]
    log(f"device time by scope, of {busy:.4f} s busy: " + "; ".join(rows))
    roots = {r: seconds_under(red, (r,)) or 0.0 for r in ENGINE_SCOPES}
    named = busy - sum(t for toks, t in red["by_tokens"].items() if label(toks, family) == "unscoped")
    log(f"under a named scope {100 * named / busy:.2f}% of busy; under the engine's programs "
        + ", ".join(f"{k} {100 * v / busy:.2f}%" for k, v in roots.items() if v))
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])
    total = sum(v for _, v in idle)
    deep = sum(v for k, v in idle if k not in ("none", "serve.step"))
    log(f"device idle in gaps of {MIN_GAP_S * 1e3:.1f} ms or more, {total:.4f} s, by the "
        f"innermost program span over each gap: "
        + "; ".join(f"{k} {v:.4f}" for k, v in idle[:10])
        + (f"; under a span deeper than serve.step {100 * deep / total:.1f}%" if total else ""))


# ------------------------------------------------- the program's own events
def spans(run: dict) -> list[dict]:
    """The program's spans that carry an id and the monotonic clock (a
    program from before PR 26 has none)."""
    events = (run.get("traced") or {}).get("program_events") or []
    return [e for e in events if e.get("kind") == "span" and "span" in e and "mono" in e]


def self_times(span_events: list[dict]) -> dict[int, float]:
    """Per span id: its duration less the part its children cover (children
    of one thread lie one after another inside their parent)."""
    out = {e["span"]: e["dur_s"] for e in span_events}
    for e in span_events:
        if e.get("parent") in out:
            out[e["parent"]] -= e["dur_s"]
    return out


def _overlap(a: tuple[float, float], merged: list[tuple[float, float]]) -> float:
    return sum(max(0.0, min(a[1], e) - max(a[0], s)) for s, e in merged)


def others_share(intervals: dict, owned: list[tuple[float, float, object]]) -> float | None:
    """Over `intervals` (key -> (start, end)): the part of each that lies
    under spans `owned` by another key, summed, over the intervals' sum."""
    total = sum(e - s for s, e in intervals.values())
    if total <= 0:
        return None
    under = 0.0
    for key, interval in intervals.items():
        merged = trace_mod._union([(s, e) for s, e, who in owned if who != key])
        under += _overlap(interval, merged)
    return under / total


def serve_requests(run: dict) -> dict | None:
    """The counted requests of a serving run on the program's clock: the
    engine numbers requests as they are submitted and the loop submits them
    in the order they are due, so the counted ones are the last
    `attempted`. Returns their first-token-to-done intervals and the window
    (opened where the first of them was submitted)."""
    events = (run.get("traced") or {}).get("program_events") or []
    first, done, seen = {}, {}, {}
    for e in events:
        rid = e.get("request")
        if rid is None or "mono" not in e:
            continue
        seen[rid] = min(seen.get(rid, e["mono"]), e["mono"])
        if e.get("name") == "serve.first_token":
            first[rid] = e["mono"]
        elif e.get("name") == "serve.complete":
            done[rid] = e["mono"]
    n = int(run.get("attempted") or 0)
    if not first or not n or not seen:
        return None
    counted = sorted(seen)[-n:]
    t_open = seen[counted[0]]
    return {
        "intervals": {r: (first[r], done[r]) for r in counted if r in first and r in done},
        "window": (t_open, t_open + float(run["host"]["window_s"])),
    }


def window_open(run: dict) -> float | None:
    """When the window opened, on the program's clock, as near as the
    events tell: a serving run's from its counted requests; a training
    run's from its recorder's last event, back over the window and the
    traced steps before it."""
    req = serve_requests(run)
    if req is not None:
        return req["window"][0]
    events = [e for e in (run.get("traced") or {}).get("program_events") or [] if "mono" in e]
    host = run.get("host") or {}
    if not events or not host.get("window_s"):
        return None
    last = max(e["mono"] + e.get("dur_s", 0.0) for e in events)
    return last - host["window_s"] - (host.get("traced") or {}).get("s", 0.0)
