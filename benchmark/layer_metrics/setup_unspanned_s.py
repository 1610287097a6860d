"""Seconds of set-up under no program span, from `obs.start` (the recorder
is made right after the chip is reached) to the end of set-up (a serving
run's first `serve.step`, a training run's first step after the warm-up:
`setup_clock.end`): the benchmark's own code (imports, weights from the
seed, corpus, schedule) and whatever the program still leaves unspanned."""
from benchmark.harness import scopes, setup_clock
from benchmark.harness.runner import log


def read(run):
    t0, t1 = setup_clock.start(run), setup_clock.end(run)
    if t0 is None or t1 is None or t1 <= t0:
        return None
    spans = scopes.spans(run)
    inside = setup_clock.clipped(spans, t0, t1)
    unspanned = (t1 - t0) - setup_clock.covered(inside)
    ids = {e["span"] for e in spans}
    roots: dict = {}
    for e in spans:
        seg = setup_clock.clipped([e], t0, t1)
        if seg and e.get("parent") not in ids:
            n, s = roots.get(e["name"], (0, 0.0))
            roots[e["name"]] = (n + 1, s + seg[0][1] - seg[0][0])
    holes = sorted(setup_clock.gaps(inside, t0, t1), key=lambda g: g[0] - g[1])[:5]
    host = run.get("host") or {}
    setup_s = (run.get("end_to_end") or {}).get("setup_s")
    reach = host.get("reach_chip_s")
    preroll = float(((run.get("cell") or {}).get("traffic") or {}).get("preroll_s", 0.0))
    log(f"set-up on the program's clock: obs.start -> end {t1 - t0:.3f} s, under no span "
        f"{unspanned:.3f} s; this run's setup_s {setup_s} against reach_chip_s {reach} + "
        f"{t1 - t0:.3f} + pre-roll {preroll}; top-level spans: "
        + "; ".join(f"{k} {n} x, {s:.3f} s"
                    for k, (n, s) in sorted(roots.items(), key=lambda kv: -kv[1][1]))
        + "; largest gaps under no span (start after obs.start, seconds): "
        + ", ".join(f"{a - t0:.3f} +{b - a:.3f}" for a, b in holes))
    return unspanned
