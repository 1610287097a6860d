"""Device time by scope and the span arithmetic, on made-up data and on a
small trace recorded on the chip (`benchmark/tools/record_scopes.py`: two
train steps and three requests through the engine of a two-layer GPT-2,
stripped to what the reduction reads, with the program's events of the
same run beside it)."""

import gzip
import json
import os

import pytest

from benchmark.harness import manifest, scopes

DATA = os.path.join(os.path.dirname(__file__), "data")
GPT2 = manifest.load_family("gpt2")  # the fixture and the made-up paths are this family's
NEW_METRICS = (
    "attn_core_share.train", "attn_core_roofline.train", "remat_share.train",
    "lm_head_loss_share.train", "decode_carry_share.serve", "kv_read_share.serve",
    "decode_host_self_share.serve", "decode_wait_prefill_share.serve", "compile_or_load_s",
)


def toks(path):
    return scopes.tokens(path)


def label(tokens):
    return scopes.label(tokens, GPT2)


# ------------------------------------------------------------- plain data
def test_tokens_and_labels():
    t = toks("jit(train_step)/transpose(jvp(GPT2))/while/body/closed_call/checkpoint/"
             "rematted_computation/h/block/attn_core/bhqk,bkhd->bqhd/dot_general:")
    assert {"jit", "train_step", "transpose", "jvp", "GPT2", "attn_core",
            "rematted_computation", "dot_general"} <= t
    assert label(t) == "attn_core"
    assert label(toks("jit(<unknown>)/serve.decode/while/body/GPT2/h/block/add")) == "block"
    assert label(toks("jit(<unknown>)/serve.decode/while")) == "serve.decode"
    assert label(toks("jit(train_step)/jvp(loss)/reduce_max")) == "loss"
    assert label(toks("jit(train_step)/reduce_sum")) == "unscoped"
    assert label(frozenset()) == "unscoped"


def test_pathless_operations_take_what_their_program_shares():
    paths = {
        "1|a": ("jit(<unknown>)/serve.decode/while/body/GPT2/h/block/kv_read/gather", 1),
        "1|b": ("jit(<unknown>)/serve.decode/GPT2/lm_head/dot_general", 1),
        "1|copy": (None, 1),       # a layout copy the compiler put in
        "2|c": ("jit(train_step)/jvp(GPT2)/h/block/c_attn/dot_general", 2),
        "1|pool": ("cache['h']['block']['cached_key']", 1),  # a copy of an argument
        "2|copy": (None, 2),
        "None|x": (None, None),
    }
    out = scopes.inherit_program_scopes(paths)
    assert out["1|copy"] == {"jit", "unknown", "serve.decode", "GPT2"}
    assert label(out["1|copy"]) == "GPT2"
    # not `block`, which its argument's name holds: the roots, and a mark
    assert out["1|pool"] == out["1|copy"] | {"argument"}
    assert "c_attn" in out["2|copy"] and "serve.decode" not in out["2|copy"]
    assert out["None|x"] == frozenset() and "kv_read" in out["1|a"]


def test_operations_are_keyed_by_the_program_they_run_in():
    modules = [(0.0, 1.0, "jit__unknown(11)"), (2.0, 3.0, "jit_step(22)"), (5.0, 6.0, "odd")]
    ops = [(0.1, 0.2, "%copy.1"), (2.5, 2.6, "%copy.1"), (4.0, 4.1, "%copy.1"), (5.5, 5.6, "%x")]
    keyed = [k for _, _, k in scopes.key_by_program(ops, modules)]
    assert keyed == ["11|%copy.1", "22|%copy.1", "None|%copy.1", "None|%x"]


def _planes():
    """One device: a decode program of two scans nested (the outer over the
    block's steps, the inner over layers) and a gap before the next."""
    ops = [
        (0.0, 10.0, "while.outer"), (0.0, 1.0, "carry.copy"),
        (1.0, 9.0, "while.inner"), (1.0, 3.0, "gather"), (3.0, 6.0, "attn"),
        (6.0, 9.0, "pool.slice"), (9.0, 10.0, "head"),
        (14.0, 16.0, "prefill.attn"),
        (16.00005, 17.0, "insert"),  # a gap under 0.1 ms is no gap
        (20.0, 21.0, "carry.copy"),
    ]
    host = [
        (9.5, 19.0, "serve.step"), (10.5, 13.0, "serve.admit"), (11.0, 12.5, "serve.prefill"),
        (9.9, 10.4, "serve.harvest"), (0.0, 30.0, "bench.engine_step"),
        (0.0, 30.0, "tpu::System::Execute=>Done"), (17.5, 18.5, "PjitFunction(step)"),
    ]
    return [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                             {"name": "XLA Modules", "events": []}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
    ]


TOKENS = {
    "while.outer": toks("jit(<unknown>)/serve.decode/while"),
    "carry.copy": toks("jit(<unknown>)/serve.decode"),
    "while.inner": toks("jit(<unknown>)/serve.decode/while/body/GPT2/while"),
    "gather": toks("jit(<unknown>)/serve.decode/while/body/GPT2/while/body/h/block/kv_read/gather"),
    "attn": toks("jit(<unknown>)/serve.decode/while/body/GPT2/while/body/h/block/attn_core/exp"),
    "pool.slice": toks("jit(<unknown>)/serve.decode/while/body/GPT2/while/body/dynamic_slice"),
    "head": toks("jit(<unknown>)/serve.decode/while/body/GPT2/lm_head/dot_general"),
    "prefill.attn": toks("jit(<unknown>)/serve.prefill/GPT2/h/block/attn_core/exp"),
    "insert": toks("jit(_page_insert_fn)/serve.insert/scatter"),
}


def test_self_time_under_nested_whiles_and_shares():
    red = scopes.reduce_planes(_planes(), TOKENS)
    assert red["busy_s"] == pytest.approx(10.0 + 2.0 + 0.99995 + 1.0)
    under = lambda *a, **k: scopes.seconds_under(red, *a, **k)  # noqa: E731
    # a while counts for itself only what its body does not cover
    assert under(("kv_read",)) == pytest.approx(2.0)
    assert under(("attn_core",)) == pytest.approx(3.0 + 2.0)
    assert under(("attn_core",), all_of=("serve.decode",)) == pytest.approx(3.0)
    assert under(("serve.decode",)) == pytest.approx(10.0 + 1.0)
    # what the decode program spends under none of the block's scopes: the
    # carried copies, the pool's slices, the whiles' own time (none here)
    assert under(("serve.decode",), none_of=GPT2.BLOCK_SCOPES) == pytest.approx(1.0 + 3.0 + 1.0)
    assert under(("serve.verify",)) is None  # no such scope in this program
    labels = dict((k, v) for k, v in scopes.by_label(red, GPT2))
    assert labels["serve.decode"] == pytest.approx(2.0)
    assert labels["GPT2"] == pytest.approx(3.0)
    assert "unscoped" not in labels


def test_idle_gaps_go_to_the_innermost_program_span():
    red = scopes.reduce_planes(_planes(), TOKENS)
    # 10 -> 14: its middle, 12, lies in serve.prefill (inside admit, inside
    # step); 17 -> 20: its middle, 18.5, in serve.step alone. The
    # benchmark's own span and the runtime's events own nothing.
    assert red["idle_by_span"] == {
        "serve.prefill": pytest.approx(4.0), "serve.step": pytest.approx(3.0),
    }
    assert {n for _, _, n in red["host_spans"]} == {
        "serve.step", "serve.admit", "serve.prefill", "serve.harvest",
    }


def test_span_self_time_and_the_wait_under_other_requests():
    spans = [
        {"span": 1, "parent": None, "dur_s": 10.0, "name": "serve.step"},
        {"span": 2, "parent": 1, "dur_s": 4.0, "name": "serve.admit"},
        {"span": 3, "parent": 2, "dur_s": 3.0, "name": "serve.prefill"},
        {"span": 4, "parent": 1, "dur_s": 5.0, "name": "serve.decode"},
        {"span": 5, "parent": 99, "dur_s": 1.0, "name": "compile"},  # parent not recorded
    ]
    assert scopes.self_times(spans) == {1: 1.0, 2: 1.0, 3: 3.0, 4: 5.0, 5: 1.0}
    # request 1 decodes over 0 -> 10, request 2 over 4 -> 8. Request 2 is
    # admitted over 2 -> 4 (its prefill 2.5 -> 3.5 inside: the union counts
    # once), request 3 over 6 -> 7, request 1 itself over 9 -> 9.5.
    owned = [(2.0, 4.0, 2), (2.5, 3.5, 2), (6.0, 7.0, 3), (9.0, 9.5, 1)]
    share = scopes.others_share({1: (0.0, 10.0), 2: (4.0, 8.0)}, owned)
    assert share == pytest.approx((2.0 + 1.0 + 1.0) / 14.0)
    assert scopes.others_share({}, owned) is None


def test_attention_flops():
    m = {"n_embd": 1280, "n_head": 20, "n_layer": 36}
    per_layer = 3 * 0.5 * 4 * 8 * 20 * 1024 * 1024 * 64
    assert GPT2.attention_flops(m, 8, 1024, 12) == 12 * 36 * per_layer


# -------------------------------------------------- the recorded fixture
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("scopes") / "scopes.xplane.pb")
    with gzip.open(os.path.join(DATA, "scopes.xplane.pb.gz"), "rb") as src:
        with open(path, "wb") as dst:
            dst.write(src.read())
    with open(os.path.join(DATA, "scopes.events.json")) as f:
        return scopes.reduce_file(path, GPT2), json.load(f)


def test_recorded_trace_device_time_by_scope(recorded):
    red, _ = recorded
    busy = red["busy_s"]
    assert 1e-4 < busy < 1e-2
    assert sum(red["by_tokens"].values()) == pytest.approx(busy, rel=0.02)
    under = lambda *a, **k: scopes.seconds_under(red, *a, **k)  # noqa: E731
    # the train step: attention forward, recomputed and backward; jax's own
    # scope on the second forward inside nn.scan; the head, the loss, and
    # the update with the norms of it that XLA fuses with it
    attn_train = under(("attn_core",), all_of=("train_step",))
    attn_again = under(("attn_core",), all_of=("train_step", scopes.REMAT))
    assert 0 < attn_again < attn_train
    assert under((scopes.REMAT,)) > attn_again
    for scope in ("lm_head", "loss", "optimizer", "c_attn", "mlp_fc", "ln_1"):
        assert under((scope,), all_of=("train_step",)) > 0, scope
    # the engine's programs by their root scopes, the cache's scatter and
    # gathers inside decode, and the layout copies of the pool (no path of
    # their own) under the program they run in
    decode = under(("serve.decode",))
    assert decode > under(("serve.prefill",)) > 0 and under(("serve.insert",)) > 0
    assert under(("kv_read",), all_of=("serve.decode",)) > 0
    assert under(("kv_write",), all_of=("serve.decode",)) > 0
    assert under(("sample",)) > 0  # the prefill's; in decode the argmax fuses into the head
    carry = under(("serve.decode",), none_of=GPT2.BLOCK_SCOPES)
    assert 0.1 * decode < carry < decode
    pathless = [k for k in red["by_tokens"] if "serve.decode" in k
                and k <= {"jit", "unknown", "serve.decode", "while", "argument"}]
    assert pathless and sum(red["by_tokens"][k] for k in pathless) > 0
    engine = sum(under((s,)) or 0.0 for s in scopes.ENGINE_SCOPES)
    train = under(("train_step",))
    assert engine + train == pytest.approx(busy, rel=0.05)


def test_recorded_trace_host_spans_own_the_gaps(recorded):
    red, events = recorded
    names = {n for _, _, n in red["host_spans"]}
    assert {"serve.step", "serve.admit", "serve.prefill", "serve.insert", "serve.decode",
            "serve.decode.dispatch", "serve.decode.fence", "serve.decode.merge",
            "serve.harvest"} <= names
    assert not any(n.startswith("bench.") for n in names)
    idle = red["idle_by_span"]
    assert sum(idle.values()) > 0
    assert any(k.startswith("serve.") and k != "serve.step" for k in idle)
    # the same spans in the program's own record, with ids and parents
    spans = [e for e in events if e.get("kind") == "span" and "span" in e]
    on_profile = sum(1 for _, _, n in red["host_spans"] if n == "serve.step")
    assert on_profile == sum(1 for e in spans if e["name"] == "serve.step") > 0
    self_s = scopes.self_times(spans)
    assert all(v >= -1e-6 for v in self_s.values())


def test_recorded_events_request_intervals(recorded):
    _, events = recorded
    run = {"traced": {"program_events": events}, "attempted": 2, "host": {"window_s": 1.0}}
    req = scopes.serve_requests(run)
    ids = sorted({e["request"] for e in events if e.get("request") is not None})
    assert sorted(req["intervals"]) == ids[-2:]  # the last two submitted
    assert all(a <= b for a, b in req["intervals"].values())
    assert req["window"][1] - req["window"][0] == 1.0
    assert scopes.window_open(run) == req["window"][0]


# ---------------------------------------------------------- the new readers
@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_new_reader_finds_nothing_without_a_trace(metric):
    read = manifest.load_reader(metric)
    untraced = {"traced": None, "host": {"window_s": 51.0}, "attempted": 10,
                "cell": {"name": "no-such-cell", "traffic": {}, "config": {"model": {}},
                         "family": GPT2}}
    assert read(untraced) is None
    # a traced run of a program from before the spans: events without ids
    # or the monotonic clock, and no trace file to be found
    old = dict(untraced, traced={"trace": {"busy_s": 1.0}, "window_s": 1.0, "program_events": [
        {"kind": "span", "name": "serve.decode", "ts": 1.0, "dur_s": 0.5},
        {"kind": "gauge", "name": "data.host_wait_s", "ts": 1.0, "value": 0.0},
    ]})
    assert read(old) is None


def test_manifest_holds_the_nine_new_metrics():
    man = manifest.load_manifest()
    assert manifest.validate(man) == []
    entries = {m["name"]: m for m in man["per_layer"]}
    assert [m["name"] for m in man["per_layer"]][-9:] == list(NEW_METRICS)
    for name in NEW_METRICS:
        m = entries[name]
        assert m["workloads"] and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert entries["compile_or_load_s"]["moves"] == "setup_s"
    assert entries["attn_core_roofline.train"]["better"] == "higher"
