"""From a profiler trace (`.xplane.pb`) to device busy time, the device
operations that took most time, and the idle gaps by what the host was
doing. Read with nothing but `jax.profiler.ProfileData`."""

from __future__ import annotations

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."
MIN_GAP_S = 1e-4


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Per name, the time of its events less the time of events nested in
    them (a `while` spans its body's operations)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [end, name, self]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, self_s = stack.pop()
            out[name] = out.get(name, 0.0) + self_s

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and "SparseCore" not in name


def short_name(name: str) -> str:
    """`%fusion.394 = bf16[8,20,1024,1024]{...} fusion(...)` (the device
    lines carry whole HLO instructions) -> `fusion.394 bf16[8,20,1024,1024]`."""
    if " = " not in name:
        return name
    op, rest = name.split(" = ", 1)
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{op.lstrip('%')} {shape}"[:96]


def _events(line) -> list[tuple[float, float, str]]:
    return [
        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9, short_name(ev.name))
        for ev in line.events
    ]


def reduce_planes(planes: list[dict]) -> dict:
    """The reduction proper, on plain data: each plane is
    {"name", "lines": [{"name", "events": [(start_s, end_s, name)]}]}."""
    busy, ops_total, modules = [], {}, {}
    gaps_by_host: dict[str, float] = {}
    host_spans: list[tuple[float, float, str]] = []
    host_frames: list[tuple[float, float, str]] = []
    for plane in planes:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for s, e, name in line["events"]:
                    if name.startswith(ANNOTATION_PREFIX):
                        host_spans.append((s, e, name))
                    elif name.startswith("$") and e - s >= MIN_GAP_S:
                        host_frames.append((s, e, name))
    idle_sets = []
    for plane in planes:
        if not _is_device_plane(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                merged = _union([(s, e) for s, e, _ in line["events"]])
                busy.append(sum(e - s for s, e in merged))
                for name, t in _self_times(line["events"]).items():
                    ops_total[name] = ops_total.get(name, 0.0) + t
                idle_sets.append(
                    [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] - a[1] >= MIN_GAP_S]
                )
            elif line["name"] == MODULES_LINE:
                for s, e, name in line["events"]:
                    tot = modules.setdefault(name, [0.0, 0])
                    tot[0] += e - s
                    tot[1] += 1
    n_dev = max(len(busy), 1)
    for gaps in idle_sets:
        for s, e in gaps:
            mid = 0.5 * (s + e)
            owner = None
            # The innermost benchmark span over the gap's middle, else the
            # innermost Python frame the profiler saw there.
            for spans, prefix in ((host_spans, ""), (host_frames, "unattributed:")):
                over = [h for h in spans if h[0] <= mid <= h[1]]
                if over:
                    owner = prefix + min(over, key=lambda h: h[1] - h[0])[2].lstrip("$")
                    break
            owner = owner or "unattributed"
            gaps_by_host[owner] = gaps_by_host.get(owner, 0.0) + (e - s) / n_dev
    top = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "busy_s": sum(busy) / n_dev if busy else 0.0,
        "devices": len(busy),
        "device_ops": top({k: v / n_dev for k, v in ops_total.items()}),
        "idle_gaps": top(gaps_by_host),
        "modules": {k: {"s": v[0] / n_dev, "calls": v[1]} for k, v in modules.items()},
    }


def reduce_trace(path: str) -> dict:
    import jax.profiler as jp

    data = jp.ProfileData.from_file(path)
    planes = [
        {
            "name": plane.name,
            "lines": [{"name": ln.name, "events": _events(ln)} for ln in plane.lines],
        }
        for plane in data.planes
        if _is_device_plane(plane.name) or plane.name.startswith("/host:")
    ]
    return reduce_planes(planes)
