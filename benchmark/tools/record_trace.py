"""Record the small profiler trace the tests reduce
(`benchmark/tests/data/small.xplane.pb`) and print how the profiler names
its planes, lines and events on this device. Run on the chip:
`chiprun -- python benchmark/tools/record_trace.py`."""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    import jax
    import jax.numpy as jnp

    def body(x, _):
        return jnp.tanh(x @ x) * 0.5, None

    step = jax.jit(lambda x: jax.lax.scan(body, x, None, length=3)[0])
    x = jnp.ones((512, 512), jnp.bfloat16)
    step(x).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    jax.profiler.start_trace(out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            x = step(x)
            x.block_until_ready()
        with jax.profiler.TraceAnnotation("bench.pause"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(ROOT, "chiprun_out", "small.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[:4]:
                print("     ", ev.name, ev.start_ns, ev.duration_ns, list(ev.stats)[:8])
    from benchmark.harness import trace

    print(trace.reduce_trace(path))


if __name__ == "__main__":
    main()
