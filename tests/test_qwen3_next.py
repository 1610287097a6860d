"""Qwen3-Next (``tpuflow/models/qwen3_next.py``), its delta rule
(``tpuflow/ops/gated_delta.py``) and the engine's per-slot state against the
plain reference of ``benchmark/families/qwen3_next.py`` (the recurrence token
by token, no cache, a dense sum over the experts held), on seeded weights at
the family's ``test`` width, float32 on both sides.

Tolerances. Program and reference differ by the order of their float32 sums
alone: a logit agrees to 2e-5 (read: 1e-6, logits up to 3.5), the chunked
and the one-token forms of the rule agree with the recurrence to 2e-5 (read:
5e-7), and a served token lies no further below the reference's best than
1e-4 (read: 0, it *is* the reference's argmax). The reference with float8 or
bfloat16 products reads 0.06 to 0.2 of a logit from itself, with the state
rounded to bfloat16 after every token 7e-3: each fails the logit tolerance
by two orders of magnitude (the last tests).
"""

import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest  # noqa: E402
from benchmark.harness.reference import seed_key  # noqa: E402
from tpuflow import obs  # noqa: E402
from tpuflow.infer.serve import GenerationUnsupported, ServeEngine  # noqa: E402
from tpuflow.models import qwen3_next as Q  # noqa: E402
from tpuflow.models.sdar import route  # noqa: E402
from tpuflow.models.xing4 import gated_ffn, routed_experts  # noqa: E402
from tpuflow.ops import gated_delta  # noqa: E402

LOGIT_TOL = 2e-5
RULE_TOL = 2e-5
GAP_TOL = 1e-4
PAGE = 8
SEED = 7
FAM = manifest.load_family("qwen3_next")
TC = FAM.test_config()
M = TC["model"]
CELL = "serve-qwen3next-hybrid"


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: FAM.make_params(M, k))(seed_key(SEED))


@jax.jit
def _ref(params, ids):
    return FAM.forward_logits(params, ids, M)


def ref_logits(params, ids):
    """The reference's logits of one sequence, computed at 64 positions: a
    causal model's earlier positions do not see what lies after them."""
    n = len(ids)
    assert n <= 64
    return np.asarray(_ref(params, jnp.asarray(np.r_[ids, np.zeros(64 - n, np.int64)])))[:n]


def _prompts(rng, lengths):
    return [rng.integers(1, 250, size=n).astype(np.int32) for n in lengths]


def _engine(params, **kw):
    kw = {**TC["serve"], "buckets": [32, 48], "page_size": PAGE, **kw}
    return ServeEngine(FAM.module(M), params, **kw)


@pytest.fixture(scope="module")
def engine(params):
    eng = _engine(params)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def alone(params):
    """One slot, no prefix cache: each request served by itself."""
    return _engine(params, max_slots=1, prefix_cache=False)


# ------------------------------------------------------ (a) the delta rule
def _rule_inputs(rng, b, t, h, dk, dv, pads=()):
    """Inputs of the rule as the model makes them (q, k unit length, q
    scaled; g < 0; beta in (0, 1)); row r's first ``pads[r]`` tokens are left
    padding: q, k, v, g and beta nought there."""
    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True))

    q = unit(rng.standard_normal((b, t, h, dk))) * dk ** -0.5
    k = unit(rng.standard_normal((b, t, h, dk)))
    v = rng.standard_normal((b, t, h, dv))
    g = -np.exp(rng.uniform(-7.0, 1.0, (b, t, h)))
    beta = rng.uniform(0.0, 1.0, (b, t, h))
    for r, p in enumerate(pads):
        for a in (q, k, v, g, beta):
            a[r, :p] = 0.0
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


@pytest.mark.parametrize("t", [64, 128, 100, 37, 1])
def test_the_chunked_rule_equals_the_recurrence(t):
    rng = np.random.default_rng(t)
    q, k, v, g, beta = _rule_inputs(rng, 2, t, 3, 16, 8)
    s0 = jnp.asarray(rng.standard_normal((2, 3, 16, 8)) * 0.3, jnp.float32)
    want_o, want_s = gated_delta.recurrent(q, k, v, g, beta, s0)
    got_o, got_s = gated_delta.chunked(q, k, v, g, beta, s0)
    np.testing.assert_allclose(got_o, want_o, atol=RULE_TOL, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=RULE_TOL, rtol=0)
    s, outs = s0, []
    for i in range(t):
        o, s = gated_delta.step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], s)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, atol=RULE_TOL, rtol=0)
    np.testing.assert_allclose(s, want_s, atol=RULE_TOL, rtol=0)


@pytest.mark.parametrize("t,pads", [(100, (0, 36)), (64, (13, 0)), (70, (6, 69))])
def test_left_padding_leaves_the_state_as_the_prompt_alone_makes_it(t, pads):
    """A row's pads (g = 0, beta = 0, nought q, k, v) change nothing: from a
    zero state the chunked form ends where the recurrence over the prompt
    alone ends, and its outputs over the prompt are the same."""
    rng = np.random.default_rng(t)
    q, k, v, g, beta = _rule_inputs(rng, 2, t, 2, 16, 8, pads)
    zero = jnp.zeros((2, 2, 16, 8), jnp.float32)
    got_o, got_s = gated_delta.chunked(q, k, v, g, beta, zero)
    for r, p in enumerate(pads):
        cut = [a[r:r + 1, p:] for a in (q, k, v, g, beta)]
        want_o, want_s = gated_delta.recurrent(*cut, zero[r:r + 1])
        np.testing.assert_allclose(got_s[r], want_s[0], atol=RULE_TOL, rtol=0)
        np.testing.assert_allclose(got_o[r, p:], want_o[0], atol=RULE_TOL, rtol=0)
        assert not np.asarray(got_o[r, :p]).any()


# ------------------------------------------------ (b) the module's forward
def test_init_declares_the_tree_the_family_builds():
    """``Qwen3Next.init`` declares the family's tree path for path, shape
    for shape and dtype for dtype: the Gated DeltaNet layers' leaves
    stacked ``(periods, 3, ...)`` under ``periods/gdn/layer``, the attention
    layers' ``(periods, ...)`` under ``periods/attn``, whatever loops read
    them. Each layer has a draw of its own."""
    model = FAM.module(M)
    declared = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    built = jax.eval_shape(lambda k: FAM.make_params(M, k), seed_key(SEED))

    def leaves(tree):
        return {jax.tree_util.keystr(path): (leaf.shape, leaf.dtype)
                for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}

    assert leaves(declared) == leaves(built)
    qkvz = declared["periods"]["gdn"]["layer"]["in_qkvz"]
    assert qkvz.shape[:2] == (M["n_layer"] // M["full_attention_interval"], 3)
    assert not np.allclose(qkvz[0, 0], qkvz[0, 1]) and not np.allclose(qkvz[0, 0], qkvz[1, 0])


def test_forward_equals_the_reference(params):
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 256, size=(2, 41)).astype(np.int32)
    mine = FAM.module(M).apply({"params": params}, jnp.asarray(toks))
    ref = jax.jit(lambda p, t: FAM.forward_logits(p, t, M))(params, toks)
    np.testing.assert_allclose(mine, ref, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("quant", ["fp8", "bf16", "state_bf16"])
def test_a_lower_precision_fails_the_logit_tolerance(params, quant):
    rng = np.random.default_rng(2)
    toks = rng.integers(0, 256, size=(1, 41)).astype(np.int32)
    mine = FAM.module(M).apply({"params": params}, jnp.asarray(toks))
    low = jax.jit(lambda p, t: FAM.forward_logits(p, t, M, quant))(params, toks)
    assert float(jnp.max(jnp.abs(mine - low))) > 100 * LOGIT_TOL


def test_the_four_expert_shares_and_the_shared_expert_once_make_the_uncut_layer():
    """Expert parallelism on the CPU: a layer's routed experts held in four
    shares of 4 of 16 (``routed_experts`` with each share's range), summed,
    plus the shared expert counted once, equal the reference's feed-forward
    of the whole layer with every expert held."""
    whole = dict(M, experts_held=M["n_experts"])
    key = seed_key(SEED)
    lp = jax.jit(lambda k: FAM.layer_params(whole, k, 1, "gdn"))(key)
    f32 = {k: v.astype(jnp.float32) for k, v in lp.items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (19, M["hidden_size"]), jnp.float32)
    cfg = Q.Qwen3NextConfig(**{**M, "dtype": jnp.float32})
    idx, w = route(x, f32["router"], cfg)
    valid = jnp.ones((19,), bool)
    held = M["experts_held"]
    total = 0.0
    for lo in range(0, M["n_experts"], held):
        share = jax.jit(lambda k, lo=lo: FAM.layer_params(dict(M, experts_first=lo), k, 1, "gdn",
                                                           FAM.ROUTED))(key)
        y, sizes = routed_experts(x, idx, w, valid, share["experts_gate_up"][None],
                                  share["experts_down"][None], jnp.float32, 0, lo=lo)
        in_share = (idx >= lo) & (idx < lo + held)
        assert int(sizes.sum()) == int(in_share.sum())
        total = total + y
    gate = jax.nn.sigmoid(x @ f32["shared_gate"])
    total = total + gate * gated_ffn(x, f32["shared_gate_up"], f32["shared_down"], jnp.float32)
    want = FAM._moe(x, f32 | {n: lp[n] for n in FAM.ROUTED}, whole, lambda a: a)
    np.testing.assert_allclose(total, want, atol=LOGIT_TOL, rtol=0)


# ------------------------------ (c) prefill, then decode through slot state
def _admit(eng, prompt, slot=0, budget=8):
    req = eng.submit(prompt, max_new_tokens=budget)
    with obs.span("serve.admit", request=req.id) as sp:
        assert eng._admit_one(eng._queue.popleft(), slot, sp)
    return req


_STEPS = {}  # one jitted step a paged model's config: the engines share a geometry


def _decode_by_hand(eng, params, slot, tokens, length):
    """The paged model over ``tokens``, one a step, in slot ``slot`` from
    column ``length``, through the slot's state row; the logits of each."""
    model = eng._pmodel
    if model.config not in _STEPS:
        _STEPS[model.config] = jax.jit(lambda p, cache, tok, at, table, slot: model.apply(
            {"params": p, "cache": cache}, tok, decode=True,
            mutable=["cache", "step_sum", "step_max"], pad_lens=jnp.zeros((1,), jnp.int32),
            slot_index=at, page_table=table, state_slots=slot,
        ))
    step = _STEPS[model.config]
    out = []
    for i, tok in enumerate(tokens):
        logits, v = step(params, eng._cache, jnp.asarray([[tok]], jnp.int32),
                         jnp.asarray([length + i]), jnp.asarray(eng._page_table[slot:slot + 1]),
                         jnp.asarray([slot]))
        eng._cache = v["cache"]
        out.append(np.asarray(logits[0, 0]))
    return np.stack(out)


@pytest.fixture(scope="module")
def bare(params):
    """An engine without a prefix cache whose slots the tests fill by hand
    and run out afterwards."""
    return _engine(params, prefix_cache=False)


@pytest.mark.parametrize("length", [5, 19, 31])
def test_prefill_then_decode_through_the_slot_state_reads_the_references_logits(
        params, bare, length):
    """A prompt padded into its bucket, admitted into slot 2 (the first
    token from the prefill), then stepped through the pool and the slot's
    state row by hand: every logit equals the reference's full forward."""
    eng = bare
    rng = np.random.default_rng(length)
    prompt = _prompts(rng, (length,))[0]
    more = rng.integers(1, 250, size=6)
    req = _admit(eng, prompt, slot=2)
    seq = np.r_[prompt, req.tokens[0], more]
    ref = ref_logits(params, seq)
    assert req.tokens[0] == int(np.argmax(ref[length - 1]))
    got = _decode_by_hand(eng, params, 2, np.r_[req.tokens[0], more], length)
    np.testing.assert_allclose(got, ref[length:], atol=LOGIT_TOL, rtol=0)
    eng.run_until_idle(max_iters=20)  # the request runs out; the slot is free again
    assert req.done


def test_a_prefix_cache_hit_reads_the_same_logits_as_a_miss(params, engine):
    """Two prompts share two pages: the second admission maps them from the
    prefix cache and still prefills its whole prompt, so its state is its
    own; its logits through pool and state equal the reference's, as those
    of the first (a miss) do."""
    eng = engine
    rng = np.random.default_rng(4)
    system = _prompts(rng, (2 * PAGE,))[0]
    prompts = [np.r_[system, t] for t in _prompts(rng, (5, 9))]
    hits = eng.pool.prefix_hits
    for slot, prompt in enumerate(prompts):
        req = _admit(eng, prompt, slot=slot)
        more = rng.integers(1, 250, size=4)
        seq = np.r_[prompt, req.tokens[0], more]
        got = _decode_by_hand(eng, params, slot, seq[prompt.size:], prompt.size)
        np.testing.assert_allclose(got, ref_logits(params, seq)[prompt.size:], atol=LOGIT_TOL, rtol=0)
    assert eng.pool.prefix_hits == hits + 2
    eng.run_until_idle(max_iters=20)


def test_rows_joining_leaving_and_reusing_slots_serve_the_references_tokens(params, engine, alone):
    """Seven requests through four slots: later ones admitted beside rows
    mid-way, rows finishing inside a block, slots reused, three sharing a
    system prompt, blocks run with dead padding rows. Every served token is
    the reference's argmax (gap 0), and each request generates what it
    generates served alone."""
    base = engine.compile_stats()
    rng = np.random.default_rng(5)
    system = _prompts(rng, (16,))[0]
    prompts = _prompts(rng, (5, 12, 30)) + [np.r_[system, t] for t in _prompts(rng, (3, 11, 1))]
    prompts.append(_prompts(rng, (9,))[0])
    budgets = (9, 3, 14, 6, 11, 5, 10)
    reqs = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts[:5], budgets)]
    engine.step()
    reqs += [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts[5:], budgets[5:])]
    engine.run_until_idle(max_iters=200)
    assert engine.compile_stats() == base
    samples = [(p, np.asarray(r.tokens)) for p, r in zip(prompts, reqs)]
    assert [len(t) for _, t in samples] == list(budgets)
    got = FAM.serve_gaps(M, SEED, samples)
    assert got["tokens"] == sum(budgets) and got["widest_gap"] <= GAP_TOL
    for p, n, r in zip(prompts, budgets, reqs):
        solo = alone.submit(p, max_new_tokens=n)
        alone.run_until_idle(max_iters=100)
        assert r.tokens == solo.tokens


def test_the_state_advanced_in_place_serves_the_tokens_of_the_gathered_path(
    params, engine, monkeypatch
):
    """The decode program with the kernels that advance S and the conv
    state where they lie (``gated_delta.step_in_place``,
    ``conv_step_in_place``: the TPU's path, here through Pallas's
    interpreter) serves the tokens of the path that gathers, steps and
    scatters the rows (the CPU's), over rows joining, leaving and reusing
    slots, and the engine says which it runs."""
    rng = np.random.default_rng(8)
    prompts = _prompts(rng, (5, 12, 30, 3, 17, 9))
    budgets = (9, 3, 14, 6, 11, 5)

    def serve(eng):
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[:4], budgets)]
        eng.step()
        reqs += [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[4:], budgets[4:])]
        eng.run_until_idle(max_iters=200)
        return [r.tokens for r in reqs]

    want = serve(engine)
    assert engine.state_step == "xla" and engine.ledger.state_step_inplace == 0
    monkeypatch.setattr(gated_delta, "in_place", lambda: True)
    eng = _engine(params)
    assert eng.state_step == "inplace" and eng.ledger.state_step_inplace == 1
    assert serve(eng) == want
    assert [len(t) for t in want] == list(budgets)


def test_a_dead_row_never_touches_a_live_slots_state(params):
    """Three live rows run at the ladder's 4-row shape: the fourth row is
    padding. Slot 3, free, holds a sentinel in its state rows; after the
    block it holds it still, and only the live slots' rows and the trash
    row moved."""
    eng = _engine(params, prefix_cache=False)
    rng = np.random.default_rng(6)
    for slot, p in enumerate(_prompts(rng, (6, 10, 14))):
        _admit(eng, p, slot=slot, budget=20)
    eng._cache = {**eng._cache, **{
        k: v.at[:, 3].set(-7.0) for k, v in eng._cache.items() if k in eng.slot_state
    }}
    before = {k: np.asarray(eng._cache[k]) for k in eng.slot_state}
    rows, _ = eng._decode_rung(eng._live.copy())
    assert len(rows) == 4 and not eng._live[rows[3]]
    eng._run_decode_block(False)
    for k in eng.slot_state:
        after = np.asarray(eng._cache[k])
        assert (after[:, 3] == -7.0).all(), k
        for s in range(3):
            assert not np.array_equal(after[:, s], before[k][:, s]), (k, s)


# ----------------------------- (d) what the engine counts, what it refuses
def test_the_slot_state_is_declared_counted_and_sown(params, tmp_path):
    """The cache's state leaves hold max_slots + 1 rows; the gauge and the
    ledger's summary say how many bytes, and ``serve.init`` and a second
    gauge how the decode program advances them; every `serve.decode` span
    carries `state_rows`, the live rows a step moved on summed over the
    block."""
    obs.configure(str(tmp_path))
    try:
        eng = _engine(params, max_slots=2, prefix_cache=False)
        cfg = eng._pmodel.config
        want = sum(4 * np.prod(s) for s in cfg.state_shapes(3).values())
        assert eng._slot_axis == {"['conv']": 1, "['state']": 1}
        assert eng.ledger.snapshot()["slot_state_bytes"] == want
        assert eng.ledger.snapshot()["state_step_inplace"] == 0
        rng = np.random.default_rng(7)
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(_prompts(rng, (4, 9)), (6, 3))]
        eng.run_until_idle(max_iters=50)
        assert all(r.done for r in reqs)
    finally:
        obs.configure(None)
    events = []
    for path in glob.glob(os.path.join(str(tmp_path), "*.jsonl")):
        with open(path) as f:
            events += [json.loads(line) for line in f if line.strip()]
    gauges = [e for e in events if e.get("name") == "serve.slot_state_bytes"]
    assert gauges and gauges[-1]["value"] == want
    # Off the TPU the decode program gathers, steps and scatters the rows.
    steps = [e for e in events if e.get("name") == "serve.state_step_inplace"]
    assert steps and steps[-1]["value"] == 0
    init = [e for e in events if e.get("kind") == "span" and e.get("name") == "serve.init"]
    assert init and init[-1]["state_step"] == "xla"
    blocks = [e for e in events if e.get("kind") == "span" and e.get("name") == "serve.decode"]
    # Tokens after the prefill's first, one a live row a step.
    assert sum(e["state_rows"] for e in blocks) == (6 - 1) + (3 - 1)
    assert all("experts_touched" in e for e in blocks)


@pytest.mark.parametrize("option,value", [
    ("quant", "weight_only"), ("speculative", 2), ("kv_store_dir", "store"),
    ("kv_host_mb", 1.0), ("generation", {"kind": "block_diffusion", "mask_id": 5}),
])
def test_an_option_that_skips_or_replays_tokens_is_refused(params, tmp_path, option, value):
    if option == "kv_store_dir":
        value = str(tmp_path / value)
    with pytest.raises(GenerationUnsupported, match="per-slot state"):
        _engine(params, max_slots=1, **{option: value})


def test_prefill_export_is_refused(engine):
    with pytest.raises(GenerationUnsupported, match="per-slot state"):
        engine.prefill_export(np.arange(1, 9, dtype=np.int32))


# --------------------------------------------- (e) the configuration file
def test_the_configuration_states_both_parameter_counts_and_every_published_number():
    cell = manifest.load_cell(CELL)
    cfg = cell["config"]
    assert FAM.check_config(cfg) == []
    assert cfg["parameters"] == 3_667_251_328 and cfg["parameters_published"] == 79_674_391_296
    whole = dict(cfg["model"], n_layer=48, experts_held=512, vocab_size=151_936)
    assert FAM.n_params(whole) == 79_674_391_296
    for key, value in (("hidden_size", 1024), ("num_experts_per_tok", 8), ("head_dim", 128)):
        assert FAM.check_config({**cfg, key: value})
    assert FAM.check_config({**cfg, "reduced": cfg["reduced"][1:]})
    assert cfg["num_experts"] == 128 and cfg["published"]["num_experts"] == 512
    assert FAM.check_config({**cfg, "num_experts": 512})  # not what the model holds
    m = cfg["model"]
    assert FAM.state_step_bytes(m, 1) == 2 * 6 * (32 * 128 * 128 + 3 * 8192) * 4
    assert FAM.routed_experts_held(m) == 8 * 128
