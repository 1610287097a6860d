"""Seconds of the engine's warm-up: the `serve.warmup` spans that end
before the window opens. Logs their children (`serve.warmup.program`, one a
program's first run, PR 39) by program and shape, with the seconds of
`trace`, `lower` and `compile` under each."""
from benchmark.harness import scopes, setup_clock
from benchmark.harness.runner import log


def _shape(e) -> str:
    if "bucket" in e:
        return f"@{e['bucket']}"
    if "rows" in e:
        return f"@{e['rows']}x{e['pages']}p"
    return ""


def read(run):
    spans = scopes.spans(run)
    t_open = scopes.window_open(run)
    warm = [
        e for e in spans if e["name"] == "serve.warmup"
        and (t_open is None or e["mono"] + e["dur_s"] <= t_open)
    ]
    if not warm:
        return None
    total = sum(e["dur_s"] for e in warm)
    ids = {e["span"] for e in warm}
    programs = {e["span"]: e for e in spans
                if e["name"] == "serve.warmup.program" and e.get("parent") in ids}
    under: dict = {}
    for e in spans:
        if e["name"] in setup_clock.COMPILE_STAGES and e.get("parent") in programs:
            under.setdefault((e["parent"], e["name"]), []).append(
                (e["mono"], e["mono"] + e["dur_s"]))
    rows: dict = {}
    for sid, e in programs.items():
        row = rows.setdefault(e["program"] + _shape(e), [0, 0.0, 0.0, 0.0, 0.0])
        row[0] += 1
        row[1] += e["dur_s"]
        for i, stage in enumerate(setup_clock.COMPILE_STAGES, start=2):
            row[i] += setup_clock.covered(under.get((sid, stage), []))
    spent = sum(r[1] for r in rows.values())
    log(f"serve.warmup {total:.3f} s, {spent:.3f} s of it in {len(programs)} programs' first "
        "runs (program: count, seconds; of them trace / lower / compile): "
        + "; ".join(f"{k} {n} x, {s:.3f} s ({t:.3f} / {lo:.3f} / {c:.3f})"
                    for k, (n, s, t, lo, c) in rows.items()))
    return total
