"""Spans on the profiler's clock and scopes at the model's layer boundaries
(ISSUE 26): span identity and nesting, the disabled path, a program span on
the `/host:` plane of a CPU profile, the named scopes in the lowered
programs (metadata only), the engine step's span tree, and the set-up
spans."""

import contextlib
import glob
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuflow import obs
from tpuflow.infer.serve import ServeEngine
from tpuflow.models.gpt2 import GPT2, GPT2Config
from tpuflow.train import TrainState, make_optimizer, make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def obs_reset(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUFLOW_HOME", str(tmp_path / "home"))
    monkeypatch.delenv("TPUFLOW_OBS_DIR", raising=False)
    obs.configure(None)
    yield
    obs.configure(None)


def _events(directory):
    obs.flush()
    out = []
    for path in glob.glob(os.path.join(directory, "events.p*.jsonl")):
        out += obs.read_events(path)
    return out


def _spans(directory, name=None):
    return [
        e for e in _events(directory)
        if e["kind"] == "span" and (name is None or e["name"] == name)
    ]


# ------------------------------------------------------------ span identity
def test_span_ids_and_parents_nest_per_thread_and_survive_an_exception(tmp_path):
    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    started = threading.Barrier(2, timeout=10)

    def worker(tag):
        with obs.span("flow.step", who=tag):
            started.wait()  # both roots are open at once, on two threads
            with obs.span("train.epoch", who=tag):
                with obs.span("ckpt.save", who=tag):
                    pass
            with pytest.raises(RuntimeError):
                with obs.span("ckpt.restore", who=tag):
                    raise RuntimeError("boom")
            # the stack unwound past the failed span: a sibling after it
            # is the root's child, not the failed span's
            with obs.span("train.compile", who=tag):
                pass

    threads = [threading.Thread(target=worker, args=(t,)) for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    spans = _spans(d)
    assert len({e["span"] for e in spans}) == len(spans) == 10
    for tag in ("a", "b"):
        mine = {e["name"]: e for e in spans if e["who"] == tag}
        root = mine["flow.step"]
        assert root["parent"] is None  # never the other thread's open root
        assert mine["train.epoch"]["parent"] == root["span"]
        assert mine["ckpt.save"]["parent"] == mine["train.epoch"]["span"]
        assert mine["ckpt.restore"]["parent"] == root["span"]
        assert mine["ckpt.restore"]["error"] == "RuntimeError"
        assert mine["train.compile"]["parent"] == root["span"]
        for e in mine.values():
            assert e["mono"] >= root["mono"]
            assert e["mono"] + e["dur_s"] <= root["mono"] + root["dur_s"] + 1e-6
    # every kind of event carries the monotonic clock beside the wall clock
    obs.event("train.report", step=1)
    assert all("mono" in e and "ts" in e for e in _events(d))


def test_disabled_span_allocates_nothing_and_the_recorder_imports_no_jax():
    assert not obs.enabled()
    s = obs.span("serve.step")
    assert s is obs.span("serve.admit", request=7) is obs.span("data.wait", hit=True)
    for _ in range(100):  # warm whatever the interpreter caches
        with obs.span("serve.decode", slots=1, spec=False, quant=False):
            pass
    before = sys.getallocatedblocks()
    for _ in range(10_000):
        with obs.span("serve.decode.fence"):
            pass
    assert abs(sys.getallocatedblocks() - before) < 50
    # Neither importing the package nor an ENABLED span imports jax: the
    # annotation is taken only where the process has jax loaded already.
    code = (
        "import sys, tempfile\n"
        "from tpuflow import obs\n"
        "with obs.span('flow.step'): pass\n"
        "obs.configure(tempfile.mkdtemp())\n"
        "with obs.span('flow.step', request=1): pass\n"
        "obs.configure(None)\n"
        "assert 'jax' not in sys.modules, 'the recorder imported jax'\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("TPUFLOW_OBS_DIR", None)
    done = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_a_program_span_lands_on_the_host_plane_of_a_profile(tmp_path):
    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=options)
    try:
        with obs.span("serve.step"):
            with obs.span("serve.decode.fence"):
                jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("serve.step", "serve.decode.fence"):
                    found[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns)
    assert set(found) == {"serve.step", "serve.decode.fence"}
    outer, inner = found["serve.step"], found["serve.decode.fence"]
    assert outer[0] <= inner[0] and inner[1] <= outer[1]  # nested on one clock
    # (and a `compile` span, where an earlier test registered the listener)
    assert {e["name"] for e in _spans(d)} >= {"serve.step", "serve.decode.fence"}


# ------------------------------------------------- scopes in the programs
CFG = GPT2Config.small_test(
    n_ctx=32, n_layer=2, scan_layers=True, remat=True, dropout=0.0
)


@pytest.fixture(scope="module")
def model_params():
    model = GPT2(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def engine(model_params):
    model, params = model_params
    eng = ServeEngine(model, params, max_slots=2, paged=True, page_size=8,
                      buckets=[16], decode_block=2, prefix_cache=True)
    eng.warmup()
    return eng


def _train_lowered(model_params):
    model, params = model_params
    state = TrainState.create(
        apply_fn=model.apply, params=params, tx=make_optimizer(learning_rate=1e-3)
    )
    batch = {k: jnp.zeros((2, 16), jnp.int32) for k in ("x", "y")}
    return make_train_step(donate=False).lower(state, batch, jax.random.PRNGKey(1))


def _decode_lowered(engine):
    args = [engine.params, engine._cache, engine._tok, engine._lengths, engine._pads,
            engine._remaining, engine._live, engine._eos, jnp.asarray(engine._page_table)]
    return engine._decode.lower(*args)


def _scope_paths(lowered) -> set[str]:
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _instructions(lowered) -> list[str]:
    """The program's operations in order, metadata aside."""
    ops = []
    for line in lowered.as_text().splitlines():
        m = re.search(r"=\s+\"?([a-z_]+\.[a-z_.]+)\"?[ (<]", line)
        if m:
            ops.append(m.group(1))
    return ops


def test_train_step_carries_every_scope_the_recomputed_forward_included(model_params):
    paths = _scope_paths(_train_lowered(model_params))

    def under(*parts):
        return any(all(p in path for p in parts) for path in paths)

    for scope in ("attn_core", "lm_head", "optimizer", "c_attn", "c_proj",
                  "mlp_fc", "mlp_proj", "ln_1", "ln_2", "ln_f"):
        assert under(scope), scope
    assert any(re.search(r"jvp\(loss\)", p) for p in paths)  # forward
    assert any(re.search(r"transpose\(jvp\(loss\)\)", p) for p in paths)  # backward
    # jax.checkpoint's own scope on the second forward, inside nn.scan
    assert under("checkpoint/rematted_computation", "attn_core")
    assert under("checkpoint/rematted_computation", "mlp_fc")


def test_decode_program_carries_the_serving_scopes(engine):
    paths = _scope_paths(_decode_lowered(engine))
    for scope in ("serve.decode", "attn_core", "kv_write", "kv_read", "lm_head", "sample",
                  "c_attn", "mlp_proj", "ln_f"):
        assert any(scope in p.split("/") for p in paths), scope
    prefill = engine._prefill.lower(
        engine.params, jnp.zeros((1, 16), jnp.int32), jnp.zeros((1,), jnp.int32), chunk=16
    )
    pre = _scope_paths(prefill)
    assert any("serve.prefill" in p.split("/") for p in pre)
    assert any("sample" in p.split("/") for p in pre)
    insert = engine._insert.lower(
        engine._cache, engine._row_template(), jnp.zeros((engine.pages_per_slot,), jnp.int32),
        jnp.int32(0), jnp.zeros((engine.pages_per_slot,), bool),
    )
    assert any("serve.insert" in p.split("/") for p in _scope_paths(insert))


def test_scopes_change_metadata_only(model_params, engine, monkeypatch):
    """The same programs lowered with every named scope taken out have the
    same operations in the same order: a scope costs nothing with tracing
    off."""
    scoped_train = _instructions(_train_lowered(model_params))
    scoped_decode = _instructions(_decode_lowered(engine))
    assert len(scoped_train) > 500 and len(scoped_decode) > 100
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    model, params = model_params
    bare = ServeEngine(model, params, max_slots=2, paged=True, page_size=8,
                       buckets=[16], decode_block=2, prefix_cache=True)
    assert not any("attn_core" in p for p in _scope_paths(_decode_lowered(bare)))
    assert _instructions(_train_lowered(model_params)) == scoped_train
    assert _instructions(_decode_lowered(bare)) == scoped_decode


# ------------------------------------------------------ the engine's spans
def test_engine_step_span_tree_and_request_ids(engine, tmp_path):
    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    before = engine.compile_stats()
    reqs = [engine.submit(np.arange(1, 1 + n, dtype=np.int32), max_new_tokens=4)
            for n in (5, 9, 12)]
    engine.run_until_idle()
    assert engine.compile_stats() == before  # spans and scopes compile nothing
    events = _events(d)
    spans = [e for e in events if e["kind"] == "span"]
    by_id = {e["span"]: e for e in spans}
    steps = [e for e in spans if e["name"] == "serve.step"]
    assert steps and all(e["parent"] is None for e in steps)
    for step in steps:
        children = [e for e in spans if e["parent"] == step["span"]]
        assert {e["name"] for e in children} <= {"serve.admit", "serve.decode", "serve.harvest"}
        assert sum(e["dur_s"] for e in children) <= step["dur_s"] + 1e-6
    # every admission span, and the prefill and insert inside it, carries
    # its request's id
    ids = {r.id for r in reqs}
    for name in ("serve.admit", "serve.prefill", "serve.insert"):
        named = [e for e in spans if e["name"] == name]
        assert {e["request"] for e in named} == ids, name
    for e in spans:
        if e["name"] in ("serve.prefill", "serve.insert"):
            parent = by_id[e["parent"]]
            assert parent["name"] == "serve.admit" and parent["request"] == e["request"]
    admits = [e for e in spans if e["name"] == "serve.admit"]
    assert all(e["admitted"] and "queue_wait_s" in e and "slot" in e for e in admits)
    # one decode span whatever the numeric path, split in three
    decodes = [e for e in spans if e["name"] == "serve.decode"]
    assert decodes and all(e["quant"] is False and e["spec"] is False for e in decodes)
    for dec in decodes:
        kids = [e["name"] for e in spans if e["parent"] == dec["span"]]
        assert kids == ["serve.decode.dispatch", "serve.decode.fence", "serve.decode.merge"]
    assert not [e for e in events if e["name"] == "serve.quant_decode"]
    # per request, first token and completion on the spans' clock
    first = {e["request"]: e["mono"] for e in events if e["name"] == "serve.first_token"}
    done = {e["request"]: e["mono"] for e in events if e["name"] == "serve.complete"}
    assert set(first) == set(done) == ids
    for r in reqs:
        assert first[r.id] == r.t_first and done[r.id] == r.t_done
        assert first[r.id] <= done[r.id]


# -------------------------------------------------------------- set-up spans
def test_compiles_become_spans_under_what_caused_them(tmp_path, mesh8):
    from tpuflow import dist
    from tpuflow.parallel import create_sharded_state

    dist.maybe_enable_compile_cache()  # registers the listener, once
    dist.maybe_enable_compile_cache()
    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    # recording leaves the compile cache's key alone: a recorded run loads
    # what an unrecorded one compiled
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False

    def init_fn(key):
        return {"w": jax.random.normal(key, (64, 8)) * 3.25}

    with mesh8:
        state, _ = create_sharded_state(init_fn, mesh8, jax.random.PRNGKey(0))
    jax.block_until_ready(state)
    spans = _spans(d)
    (init,) = [e for e in spans if e["name"] == "state.init"]
    compiles = [e for e in spans if e["name"] == "compile" and e["parent"] == init["span"]]
    assert len(compiles) == 1  # one listener however often it was registered
    (comp,) = compiles
    assert comp["program"] == "jit(init_fn)" and comp["cache_hit"] is None  # CPU: no cache
    assert init["mono"] <= comp["mono"] and comp["dur_s"] <= init["dur_s"]


def test_data_wait_span_beside_the_gauge(tmp_path, mesh8):
    from tpuflow.data.loader import prefetch_to_device

    d = str(tmp_path / "obs")
    obs.configure(d, proc=0)
    batches = [{"x": np.full((8, 2), i, np.int32)} for i in range(3)]
    for depth in (0, 2):
        got = list(prefetch_to_device(batches, mesh8, depth=depth, keys=("x",)))
        assert len(got) == 3
    events = _events(d)
    waits = [e for e in events if e["name"] == "data.wait"]
    gauges = [e for e in events if e["name"] == "data.host_wait_s"]
    # threaded: one more wait, for the end-of-data marker, as the gauge has
    assert len(waits) == len(gauges) == 3 + 4
    assert all(e["kind"] == "span" and "hit" in e for e in waits)
