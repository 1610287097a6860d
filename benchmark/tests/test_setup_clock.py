"""Set-up on the program's clock (PR 39): the readers `trace_lower_s`,
`warmup_s.serve` and `setup_unspanned_s` on made-up event lists."""

import pytest

from benchmark.harness import manifest, setup_clock

SETUP_METRICS = ("trace_lower_s", "warmup_s.serve", "setup_unspanned_s")


def span(sid, name, t0, t1, parent=None, **attrs):
    return {"kind": "span", "name": name, "span": sid, "parent": parent,
            "mono": t0, "dur_s": t1 - t0, **attrs}


def train_run():
    """obs.start at 100; the state's init with a trace, a nested trace, a
    lowering and a compile under it; the benchmark's own jit under no span;
    the loader's wait for a warm-up step, for the one traced step (set-up
    ends there) and for the window's two steps; the window of 10 s ends
    with the last event (so it opens at 110); one retrace on the hot path."""
    events = [
        {"kind": "event", "name": "obs.start", "mono": 100.0},
        span(1, "state.init", 101.0, 104.0),
        span(2, "trace", 101.0, 102.0, parent=1, program="outer"),
        span(3, "trace", 101.2, 101.7, parent=1, program="inner"),  # inside 2
        span(4, "lower", 102.0, 102.5, parent=1, program="jit(outer)"),
        span(5, "compile", 102.5, 103.5, parent=1, program="jit(outer)"),
        span(6, "trace", 104.5, 105.0, program="make_params"),
        span(7, "compile", 105.0, 106.0, program="jit(make_params)"),
        span(11, "data.wait", 106.5, 106.6),
        span(12, "data.wait", 108.0, 108.1),
        span(8, "data.wait", 110.0, 110.1),
        span(9, "trace", 115.0, 115.2, program="retraced"),
        span(10, "data.wait", 119.5, 120.0),
    ]
    return {"traced": {"program_events": events}, "attempted": 2,
            "host": {"window_s": 10.0, "traced": {"steps": 1, "s": 0.0}, "reach_chip_s": 12.0},
            "end_to_end": {"setup_s": 22.0},
            "cell": {"traffic": {"kind": "train_steady"}}}


def serve_run():
    """obs.start at 0; the engine's init, then its warm-up: a prefill and a
    decode block (two nested traces under it) and the reset; the open
    loop's first step at 9; two requests, the window opening at 20."""
    events = [
        {"kind": "event", "name": "obs.start", "mono": 0.0},
        span(1, "serve.init", 1.0, 2.0),
        span(10, "serve.warmup", 2.0, 8.0),
        span(11, "serve.warmup.program", 2.0, 4.0, parent=10, program="prefill", bucket=16),
        span(12, "trace", 2.0, 3.0, parent=11, program="_prefill_fn"),
        span(13, "lower", 3.0, 3.5, parent=11, program="jit(_prefill_fn)"),
        span(14, "compile", 3.5, 3.8, parent=11, program="jit(_prefill_fn)"),
        span(15, "serve.warmup.program", 4.0, 7.0, parent=10, program="decode", rows=2, pages=4),
        span(16, "trace", 4.0, 5.5, parent=15, program="_decode_fn"),
        span(17, "trace", 4.2, 4.4, parent=15, program="_paged_attention"),
        span(18, "compile", 6.0, 6.5, parent=15, program="jit(_decode_fn)"),
        span(19, "serve.warmup.program", 7.0, 8.0, parent=10, program="reset"),
        span(20, "serve.step", 9.0, 9.01),
        span(21, "serve.admit", 9.5, 9.6, request=0),
        span(22, "serve.admit", 20.0, 20.2, request=1),
        {"kind": "event", "name": "serve.first_token", "mono": 20.5, "request": 1},
        {"kind": "event", "name": "serve.complete", "mono": 21.0, "request": 1},
        span(23, "serve.admit", 25.0, 25.1, request=2),
        {"kind": "event", "name": "serve.first_token", "mono": 25.5, "request": 2},
        {"kind": "event", "name": "serve.complete", "mono": 26.0, "request": 2},
    ]
    return {"traced": {"program_events": events}, "attempted": 2,
            "host": {"window_s": 10.0, "reach_chip_s": 11.0},
            "end_to_end": {"setup_s": 30.0},
            "cell": {"traffic": {"kind": "serve_open_loop", "preroll_s": 10.0}}}


def read(metric, run):
    return manifest.load_reader(metric)(run)


def test_nested_traces_count_once_and_hot_path_retraces_are_left_out(capsys):
    # [101, 102.5] (the inner trace inside the outer, the lowering after
    # it) and [104.5, 105]; the retrace at 115 is after the window opened
    assert read("trace_lower_s", train_run()) == pytest.approx(2.0)
    out = capsys.readouterr().out
    assert "traces that started after the window opened: 1, 0.200 s" in out
    assert "trace outer 1 x, 1.000 s" in out
    # the serving run's, which end before its window opens at 20
    assert read("trace_lower_s", serve_run()) == pytest.approx(1.5 + 1.5)


def test_unspanned_plus_the_spans_union_is_the_interval(capsys):
    run = train_run()
    t0, t1 = setup_clock.start(run), setup_clock.end(run)
    assert (t0, t1) == (100.0, 108.0)
    spanned = setup_clock.covered(
        setup_clock.clipped(setup_clock.scopes.spans(run), t0, t1))
    unspanned = read("setup_unspanned_s", run)
    assert spanned == pytest.approx(3.0 + 1.5 + 0.1)
    assert unspanned + spanned == pytest.approx(t1 - t0)
    assert "reach_chip_s 12.0 + 8.000 + pre-roll 0.0" in capsys.readouterr().out
    # where the waits do not hold the window's and the traced steps, the
    # events do not show the end
    run["attempted"] = 9
    assert setup_clock.end(run) is None and read("setup_unspanned_s", run) is None
    # a serving run's set-up ends at the open loop's first step, not at
    # the first admission, nor at the window past the pre-roll
    run = serve_run()
    assert setup_clock.end(run) == 9.0
    assert read("setup_unspanned_s", run) == pytest.approx(9.0 - 7.0)


def test_gaps_are_what_no_span_covers():
    assert setup_clock.gaps([(1.0, 2.0), (1.5, 3.0), (5.0, 6.0)], 0.0, 7.0) == [
        (0.0, 1.0), (3.0, 5.0), (6.0, 7.0)]
    assert setup_clock.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_warmup_by_program_with_its_trace_lower_compile(capsys):
    assert read("warmup_s.serve", serve_run()) == pytest.approx(6.0)
    out = capsys.readouterr().out
    assert "prefill@16 1 x, 2.000 s (1.000 / 0.500 / 0.300)" in out
    assert "decode@2x4p 1 x, 3.000 s (1.500 / 0.000 / 0.500)" in out
    assert "reset 1 x, 1.000 s (0.000 / 0.000 / 0.000)" in out
    assert read("warmup_s.serve", train_run()) is None


@pytest.mark.parametrize("metric", SETUP_METRICS)
def test_a_setup_reader_finds_nothing_where_its_spans_are_absent(metric):
    assert read(metric, {"traced": None, "host": {"window_s": 51.0}, "attempted": 10}) is None
    # a program from before PR 39: no obs.start, no trace or lower spans,
    # no warm-up children; a training run has no warm-up at all
    old = train_run()
    old["traced"]["program_events"] = [
        e for e in old["traced"]["program_events"]
        if e["name"] not in ("obs.start", "trace", "lower")]
    assert read(metric, old) is None
    parent = serve_run()
    parent["traced"]["program_events"] = [
        e for e in parent["traced"]["program_events"]
        if e["name"] not in ("obs.start", "trace", "lower", "serve.warmup.program",
                             "serve.init")]
    got = read(metric, parent)
    # the parent's serve.warmup has no children, and reads all the same
    assert got == (pytest.approx(6.0) if metric == "warmup_s.serve" else None)


def test_manifest_holds_the_setup_metrics():
    man = manifest.load_manifest()
    assert manifest.validate(man) == []
    entries = {m["name"]: m for m in man["per_layer"]}
    for name in SETUP_METRICS:
        m = entries[name]
        assert (m["source"], m["moves"], m["better"], m["unit"]) == (
            "program_span", "setup_s", "lower", "s")
    assert len(entries["warmup_s.serve"]["workloads"]) == 3
    assert len(entries["trace_lower_s"]["workloads"]) == 4
    assert entries["setup_unspanned_s"]["workloads"] == entries["trace_lower_s"]["workloads"]
