"""Record the small scoped trace the tests reduce
(`benchmark/tests/data/scopes.xplane.pb.gz`, with the program's events of
the same run beside it): two train steps and a few engine steps of a
two-layer model (the `test_config()` of the family named below) with the
program's named scopes and its spans on the host plane; and print where
this JAX keeps a device operation's scope path. Run on the chip:
`chiprun -- python benchmark/tools/record_scopes.py`.

The profiler's file is 1.8 MB at this size, two thirds of it the programs'
HLO (`/host:metadata`) and most of the rest stack traces in the
operations' metadata. What is kept is what the reduction reads: the device
planes' `XLA Ops` and `XLA Modules` lines, their operations' names with the
`tf_op` and `program_id` stats, and the host plane's program and benchmark
annotations. Gzipped, since HLO instruction text is most of what is left."""

import glob
import gzip
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FAMILY = "gpt2"  # the fixture the tests reduce is this family's


def _varint(x: int) -> bytes:
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not x:
            return bytes(out)


def _field(no: int, wire: int, value) -> bytes:
    key = _varint(no << 3 | wire)
    if wire == 0:
        return key + _varint(value)
    body = bytes(value)
    return key + (_varint(len(body)) if wire == 2 else b"") + body


def strip(raw: bytes) -> bytes:
    """The trace with only what `harness/scopes.py` and `harness/trace.py`
    read (field numbers: see `scopes.operation_scopes`; XLine: name = 2,
    events = 4; XEvent: metadata_id = 1)."""
    from benchmark.harness import scopes, trace

    out = b""
    for no, wire, plane in scopes.fields(memoryview(raw)):
        if no != 1:
            continue
        parts = list(scopes.fields(plane))
        name = next(bytes(v).decode() for n, _, v in parts if n == 2)
        device = trace._is_device_plane(name)
        if not device and not name.startswith("/host:CPU"):
            continue
        stat_ids = {}
        for n, _, v in parts:
            if n == 5:
                entry = dict((k, x) for k, _, x in scopes.fields(v))
                stat_ids[next(
                    (bytes(x).decode() for k, _, x in scopes.fields(entry[2]) if k == 2), ""
                )] = entry[1]
        metas = {}
        for n, _, v in parts:
            if n == 4:
                entry = dict((k, x) for k, _, x in scopes.fields(v))
                metas[entry[1]] = entry[2]

        def wanted(meta) -> bool:
            text = next((bytes(v).decode() for n, _, v in scopes.fields(meta) if n == 2), "")
            return device or bool(scopes.PROGRAM_SPAN_RE.match(text))

        used, kept = set(), b""
        for n, w, v in parts:
            if n in (1, 2, 5):
                kept += _field(n, w, v)
            elif n == 3:
                line = list(scopes.fields(v))
                lname = next((bytes(x).decode() for k, _, x in line if k == 2), "")
                if device and lname not in (trace.OPS_LINE, trace.MODULES_LINE):
                    continue
                body, events = b"", 0
                for k, lw, x in line:
                    if k != 4:
                        body += _field(k, lw, x)
                        continue
                    mid = next(val for f, _, val in scopes.fields(x) if f == 1)
                    if wanted(metas[mid]):
                        used.add(mid)
                        events += 1
                        body += _field(k, lw, x)
                if events:
                    kept += _field(3, 2, body)
        keep_stats = {stat_ids.get("tf_op"), stat_ids.get("program_id")}
        for mid in sorted(used):
            meta = b""
            for n, w, v in scopes.fields(metas[mid]):
                if n == 5:
                    sid = next(val for f, _, val in scopes.fields(v) if f == 1)
                    if sid not in keep_stats:
                        continue
                if n != 3:  # `metadata` bytes: nothing reads them
                    meta += _field(n, w, v)
            kept += _field(4, 2, _field(1, 0, mid) + _field(2, 2, meta))
        out += _field(1, 2, kept)
    return out


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import manifest
    from tpuflow import obs
    from tpuflow.infer.serve import ServeEngine
    from tpuflow.train import TrainState, make_optimizer, make_train_step

    out = os.path.join(ROOT, "chiprun_out", "scopes_trace")
    shutil.rmtree(out, ignore_errors=True)
    obs_dir = os.path.join(ROOT, "chiprun_out", "scopes_obs")
    shutil.rmtree(obs_dir, ignore_errors=True)
    # No compile cache: its key leaves metadata out, so a cached executable
    # carries the scope names of whichever version of the code compiled it
    # (the first fixture loaded an older step and lacked a scope).
    jax.config.update("jax_enable_compilation_cache", False)
    obs.configure(obs_dir)
    family = manifest.load_family(FAMILY, ROOT)
    model = family.module(family.test_config()["model"])
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer(learning_rate=3e-4)
    )
    step = make_train_step(donate=False)
    batch = {k: jnp.ones((4, 64), jnp.int32) for k in ("x", "y")}
    rng = jax.random.PRNGKey(1)
    jax.block_until_ready(step(state, batch, rng))
    engine = ServeEngine(model, params, max_slots=4, paged=True, buckets=[48, 64],
                         decode_block=4, prefix_cache=True)
    engine.warmup()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(2):
        jax.block_until_ready(step(state, batch, rng)[1]["loss"])
    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (20, 33, 40)]
    handles = [engine.submit(p, max_new_tokens=6) for p in prompts]
    while not all(h.done for h in handles):
        engine.step()
    jax.profiler.stop_trace()
    obs.configure(None)
    path = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    kept = os.path.join(ROOT, "chiprun_out", "scopes.xplane.pb")
    with open(path, "rb") as fh:
        stripped = strip(fh.read())
    with open(kept, "wb") as fh:
        fh.write(stripped)
    with open(kept + ".gz", "wb") as raw, gzip.GzipFile(fileobj=raw, mode="wb", mtime=0) as fh:
        fh.write(stripped)
    events = []
    for f in glob.glob(os.path.join(obs_dir, "*.jsonl")):
        with open(f) as fh:
            events += [json.loads(line) for line in fh if line.strip()]
    with open(os.path.join(ROOT, "chiprun_out", "scopes.events.json"), "w") as fh:
        json.dump(events, fh)
    print("SIZE", os.path.getsize(path), "bytes as recorded,", os.path.getsize(kept),
          "kept,", os.path.getsize(kept + ".gz"), "gzipped;", len(events), "program events")

    # What `ProfileData` shows of a device operation, and what the file holds.
    data = jax.profiler.ProfileData.from_file(path)
    for ev in (e for pl in data.planes if pl.name.startswith("/device:TPU:")
               for ln in pl.lines if ln.name == "XLA Ops" for e in ln.events):
        if "fusion" in ev.name:
            print("EVENT", ev.name[:200], "STATS", dict(ev.stats))
            break
    from benchmark.harness import scopes

    whole, small = scopes.reduce_file(path, family), scopes.reduce_file(kept, family)
    assert whole["by_tokens"] == small["by_tokens"], "stripping changed the reduction"
    assert whole["idle_by_span"] == small["idle_by_span"]
    scoped = {k: sorted(v) for k, v in scopes.operation_scopes(kept).items() if "attn_core" in v}
    print("ITS METADATA'S tf_op, AS TOKENS", json.dumps(dict(list(scoped.items())[:2]))[:900])
    print("HOST SPANS", json.dumps(sorted({n for _, _, n in small["host_spans"]})))


if __name__ == "__main__":
    main()
