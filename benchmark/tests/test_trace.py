"""The reduction of a profiler trace, on a small trace recorded on the chip
(`benchmark/tools/record_trace.py`: three calls of a scanned matmul with a
10 ms pause after each) and on made-up planes."""

import os

from benchmark.harness import trace

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_small_recorded_trace():
    r = trace.reduce_trace(SMALL)
    assert r["devices"] == 1
    assert 1e-5 < r["busy_s"] < 1e-3
    names = [n for n, _ in r["device_ops"]]
    assert names[0].startswith("fusion.8 bf16[512,512]")
    # the while spans its body: its self time is next to nothing
    by_name = dict((n, s) for n, s in r["device_ops"])
    whiles = [s for n, s in by_name.items() if n.startswith("while")]
    assert whiles and max(whiles) < 0.1 * r["busy_s"]
    assert abs(sum(by_name.values()) - r["busy_s"]) < 0.05 * r["busy_s"]
    (module, stat), = r["modules"].items()
    assert module.startswith("jit__lambda") and stat["calls"] == 3
    # the gaps between calls belong to the benchmark's own span over them
    assert r["idle_gaps"][0][0] == "bench.pause" and r["idle_gaps"][0][1] > 0.015


def test_busy_is_a_union_and_gaps_go_to_the_innermost_host_span():
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                (0.0, 1.0, "while"), (0.0, 0.4, "a"), (0.5, 1.0, "b"), (3.0, 4.0, "a")]},
            {"name": "XLA Modules", "events": [(0.0, 1.0, "jit_step(1)"), (3.0, 4.0, "jit_step(1)")]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            (0.9, 3.1, "bench.outer"), (1.5, 2.5, "bench.save"), (0.0, 5.0, "$file.py:1 f")]}]},
    ]
    r = trace.reduce_planes(planes)
    assert r["busy_s"] == 2.0
    ops = dict((n, s) for n, s in r["device_ops"])
    assert abs(ops["a"] - 1.4) < 1e-9 and abs(ops["b"] - 0.5) < 1e-9
    assert abs(ops["while"] - 0.1) < 1e-9
    assert r["idle_gaps"] == [["bench.save", 2.0]]
    assert r["modules"]["jit_step(1)"] == {"s": 2.0, "calls": 2}


def test_a_trace_with_no_device_plane_reads_nothing():
    r = trace.reduce_planes([{"name": "/host:CPU", "lines": []}])
    assert r["busy_s"] == 0.0 and r["device_ops"] == []


def test_short_name():
    long = ("%fusion.394 = bf16[8,20,1024,1024]{2,3,1,0:T(8,128)(2,1)} fusion(f32[8] %x), "
            "kind=kLoop")
    assert trace.short_name(long) == "fusion.394 bf16[8,20,1024,1024]"
    assert trace.short_name("bench.step") == "bench.step"
