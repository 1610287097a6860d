"""Olmo-Hybrid (``tpuflow/models/olmo_hybrid.py`` on the shared stack of
``tpuflow/models/hybrid.py``), its delta rule with a write strength up to 2
and a state wider than it is deep (``tpuflow/ops/gated_delta.py``), and the
engine's per-slot state against the plain reference of
``benchmark/families/olmo_hybrid.py`` (post-norm blocks, the recurrence
token by token with ``β = 2 sigmoid(b)``, no rotary embedding, a dense
SwiGLU; no cache), on seeded weights at the family's ``test`` width,
float32 on both sides.

Tolerances, as ``tests/test_qwen3_next.py`` sets them: program and
reference differ by the order of their float32 sums alone, a logit agrees
to 2e-5 (read: 3e-6, logits up to 4), the chunked and one-token forms of
the rule agree with the recurrence to 2e-5 (read: 1e-6 at keys of 96 and
values of 192, β up to 2), and a served token lies no further below the
reference's best than 1e-4 (read: 0). The reference with float8 or
bfloat16 products, with β = sigmoid(b), or with a rotary embedding in its
attention reads 0.03 to 0.6 of a logit from the program: each fails the
logit tolerance by two orders of magnitude.
"""

import dataclasses
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
from tpuflow.infer.serve import ServeEngine  # noqa: E402
from tpuflow.models.hybrid import HybridLM  # noqa: E402
from tpuflow.models.olmo_hybrid import OlmoHybrid  # noqa: E402
from tpuflow.ops import gated_delta  # noqa: E402

LOGIT_TOL = 2e-5
RULE_TOL = 2e-5
GAP_TOL = 1e-4
PAGE = 8
SEED = 11
FAM = manifest.load_family("olmo_hybrid")
TC = FAM.test_config()
M = TC["model"]
CELL = "serve-olmohybrid-chat"


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


# ------------------------------------------------------ (a) the delta rule
def _rule_inputs(rng, b, t, h, dk, dv, beta_range=(0.0, 2.0), pads=()):
    """Inputs of the rule as the model makes them (q, k unit length, q
    scaled; g < 0; beta over ``beta_range``, up to 2 as Olmo-Hybrid's);
    row r's first ``pads[r]`` tokens are left padding."""
    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True))

    q = unit(rng.standard_normal((b, t, h, dk))) * dk ** -0.5
    k = unit(rng.standard_normal((b, t, h, dk)))
    v = rng.standard_normal((b, t, h, dv))
    g = -np.exp(rng.uniform(-7.0, 1.0, (b, t, h)))
    beta = rng.uniform(*beta_range, (b, t, h))
    for r, p in enumerate(pads):
        for a in (q, k, v, g, beta):
            a[r, :p] = 0.0
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


@pytest.mark.parametrize("t,beta_range", [
    (64, (0.0, 2.0)), (128, (0.0, 2.0)), (100, (0.0, 2.0)), (37, (0.0, 2.0)), (1, (0.0, 2.0)),
    (128, (1.9, 2.0)), (100, (1.99, 2.0)),
])
def test_the_chunked_and_one_token_rules_equal_the_recurrence_at_olmos_state(t, beta_range):
    """Keys of 96, values of 192 (S is not square), β over (0, 2) and near
    2, chunks of 64: the triangular solve meets entries of A up to 2."""
    rng = np.random.default_rng(t)
    q, k, v, g, beta = _rule_inputs(rng, 2, t, 3, 96, 192, beta_range)
    s0 = jnp.asarray(rng.standard_normal((2, 3, 96, 192)) * 0.3, jnp.float32)
    want_o, want_s = gated_delta.recurrent(q, k, v, g, beta, s0)
    got_o, got_s = gated_delta.chunked(q, k, v, g, beta, s0, 64)
    np.testing.assert_allclose(got_o, want_o, atol=RULE_TOL, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=RULE_TOL, rtol=0)
    s, outs = s0, []
    for i in range(min(t, 16)):
        o, s = gated_delta.step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i], s)
        outs.append(o)
    n = len(outs)
    head_o, head_s = gated_delta.recurrent(*(a[:, :n] for a in (q, k, v, g, beta)), s0)
    np.testing.assert_allclose(jnp.stack(outs, 1), head_o, atol=RULE_TOL, rtol=0)
    np.testing.assert_allclose(s, head_s, atol=RULE_TOL, rtol=0)


@pytest.mark.parametrize("t,pads", [(100, (0, 36)), (70, (6, 69))])
def test_left_padding_leaves_the_wide_state_as_the_prompt_alone_makes_it(t, pads):
    rng = np.random.default_rng(t)
    q, k, v, g, beta = _rule_inputs(rng, 2, t, 2, 96, 192, pads=pads)
    zero = jnp.zeros((2, 2, 96, 192), jnp.float32)
    got_o, got_s = gated_delta.chunked(q, k, v, g, beta, zero, 64)
    for r, p in enumerate(pads):
        cut = [a[r:r + 1, p:] for a in (q, k, v, g, beta)]
        want_o, want_s = gated_delta.recurrent(*cut, zero[r:r + 1])
        np.testing.assert_allclose(got_s[r], want_s[0], atol=RULE_TOL, rtol=0)
        np.testing.assert_allclose(got_o[r, p:], want_o[0], atol=RULE_TOL, rtol=0)
        assert not np.asarray(got_o[r, :p]).any()


# ------------------------------------------------ (b) the module's forward
def test_init_declares_the_tree_the_family_builds():
    """The shared stack declares the family's tree path for path, shape for
    shape: a dense feed-forward (``gate_up``, ``down``) and no expert leaf,
    an ungated query of heads x 128, QK-norms over the whole projection."""
    model = FAM.module(M)
    assert isinstance(model, HybridLM) and isinstance(model, OlmoHybrid)
    declared = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    built = jax.eval_shape(lambda k: FAM.make_params(M, k), seed_key(SEED))

    def leaves(tree):
        return {jax.tree_util.keystr(path): (leaf.shape, leaf.dtype)
                for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}

    assert leaves(declared) == leaves(built)
    attn = declared["periods"]["attn"]
    c, hd = M["hidden_size"], M["n_head"] * M["head_dim"]
    assert attn["q"].shape == (2, c, hd) and attn["q_norm"].shape == (2, hd)
    assert "experts_gate_up" not in declared and "router" not in attn
    assert declared["periods"]["gdn"]["layer"]["gate_up"].shape == (2, 3, c, 2 * M["intermediate_size"])


def test_forward_equals_the_reference(params):
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 256, size=(2, 41)).astype(np.int32)
    mine = FAM.module(M).apply({"params": params}, jnp.asarray(toks))
    ref = jax.jit(lambda p, t: FAM.forward_logits(p, t, M))(params, toks)
    np.testing.assert_allclose(mine, ref, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("quant", ["fp8", "bf16", "beta_sigmoid", "rope"])
def test_a_lower_precision_or_other_mathematics_fails_the_logit_tolerance(params, quant):
    rng = np.random.default_rng(2)
    toks = rng.integers(0, 256, size=(1, 41)).astype(np.int32)
    mine = FAM.module(M).apply({"params": params}, jnp.asarray(toks))
    low = jax.jit(lambda p, t: FAM.forward_logits(p, t, M, quant))(params, toks)
    assert float(jnp.max(jnp.abs(mine - low))) > 100 * LOGIT_TOL


@pytest.mark.parametrize("choice,other", [
    ("block_norm", "pre"), ("gdn_beta_scale", 1.0), ("partial_rotary_factor", 0.25),
])
def test_each_choice_of_the_shared_stack_is_really_taken(params, choice, other):
    """The same weights through the stack with one choice flipped (pre-norm
    for post-norm, β = sigmoid(b), a partial rotary embedding) give other
    logits: the config's field is what the stack does."""
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, 256, size=(1, 33)).astype(np.int32))
    model = FAM.module(M)
    flipped = model.clone(config=dataclasses.replace(model.config, **{choice: other}))
    mine = model.apply({"params": params}, toks)
    theirs = flipped.apply({"params": params}, toks)
    assert float(jnp.max(jnp.abs(mine - theirs))) > 100 * LOGIT_TOL


# ------------------------------ (c) prefill, then decode through slot state
def _admit(eng, prompt, slot=0, budget=8):
    req = eng.submit(prompt, max_new_tokens=budget)
    with obs.span("serve.admit", request=req.id) as sp:
        assert eng._admit_one(eng._queue.popleft(), slot, sp)
    return req


def _decode_by_hand(eng, params, slot, tokens, length):
    """The paged model over ``tokens``, one a step, in slot ``slot`` from
    column ``length``, through the slot's state row; the logits of each."""
    model = eng._pmodel
    step = jax.jit(lambda p, cache, tok, at, table, slot: model.apply(
        {"params": p, "cache": cache}, tok, decode=True,
        mutable=["cache", "step_sum", "step_max"], pad_lens=jnp.zeros((1,), jnp.int32),
        slot_index=at, page_table=table, state_slots=slot,
    ))
    out = []
    for i, tok in enumerate(tokens):
        logits, v = step(params, eng._cache, jnp.asarray([[tok]], jnp.int32),
                         jnp.asarray([length + i]), jnp.asarray(eng._page_table[slot:slot + 1]),
                         jnp.asarray([slot]))
        eng._cache = v["cache"]
        out.append(np.asarray(logits[0, 0]))
    return np.stack(out)


def test_prefill_then_decode_through_the_slot_state_and_a_prefix_hit_read_the_references_logits(
        params, engine):
    """A prompt padded into its bucket, admitted (the first token from the
    prefill), then stepped through the pool and the slot's state row by
    hand: every logit equals the reference's full forward. A second prompt
    shares two pages with the first: its admission maps them from the
    prefix cache and still prefills its whole prompt, so its state is its
    own; its logits equal the reference's as the miss's do."""
    eng = engine
    rng = np.random.default_rng(4)
    system = _prompts(rng, (2 * PAGE,))[0]
    prompts = [np.r_[system, t] for t in _prompts(rng, (5, 11))]
    hits = eng.pool.prefix_hits
    for slot, prompt in zip((2, 0), prompts):
        req = _admit(eng, prompt, slot=slot)
        more = rng.integers(1, 250, size=5)
        seq = np.r_[prompt, req.tokens[0], more]
        ref = ref_logits(params, seq)
        assert req.tokens[0] == int(np.argmax(ref[prompt.size - 1]))
        got = _decode_by_hand(eng, params, slot, seq[prompt.size:], prompt.size)
        np.testing.assert_allclose(got, ref[prompt.size:], atol=LOGIT_TOL, rtol=0)
    assert eng.pool.prefix_hits == hits + 2
    eng.run_until_idle(max_iters=20)


def test_rows_joining_leaving_and_reusing_slots_serve_the_references_tokens(params, engine, tmp_path):
    """Seven requests through four slots: later ones admitted beside rows
    mid-way, rows finishing inside a block, slots reused, three sharing a
    system prompt. Every served token is the reference's argmax (gap 0);
    nothing compiles after warm-up; every ``serve.decode`` span carries the
    live rows' ``state_rows`` and its forward ``passes`` (what
    ``ffn_roofline.serve`` reads), and no routed count (the model has no
    experts)."""
    base = engine.compile_stats()
    rng = np.random.default_rng(5)
    system = _prompts(rng, (16,))[0]
    prompts = _prompts(rng, (5, 12, 30)) + [np.r_[system, t] for t in _prompts(rng, (3, 11, 1))]
    prompts.append(_prompts(rng, (9,))[0])
    budgets = (9, 3, 14, 6, 11, 5, 10)
    obs.configure(str(tmp_path))
    try:
        reqs = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts[:5], budgets)]
        engine.step()
        reqs += [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts[5:], budgets[5:])]
        engine.run_until_idle(max_iters=200)
    finally:
        obs.configure(None)
    assert engine.compile_stats() == base
    samples = [(p, np.asarray(r.tokens)) for p, r in zip(prompts, reqs)]
    assert [len(t) for _, t in samples] == list(budgets)
    got = FAM.serve_gaps(M, SEED, samples)
    assert got["tokens"] == sum(budgets) and got["widest_gap"] <= GAP_TOL
    events = []
    for path in glob.glob(os.path.join(str(tmp_path), "*.jsonl")):
        with open(path) as f:
            events += [json.loads(line) for line in f if line.strip()]
    blocks = [e for e in events if e.get("kind") == "span" and e.get("name") == "serve.decode"]
    assert blocks and all(e["passes"] == engine.decode_block for e in blocks)
    # Tokens after the prefill's first, one a live row a step.
    assert sum(e["state_rows"] for e in blocks) == sum(n - 1 for n in budgets)
    assert not any("experts_touched" in e for e in blocks)


# --------------------------------------------- (d) the configuration file
def test_the_configuration_states_both_parameter_counts_and_every_published_number():
    cell = manifest.load_cell(CELL)
    cfg = cell["config"]
    assert FAM.check_config(cfg) == []
    assert cfg["parameters"] == 4_100_788_944 and cfg["parameters_published"] == 7_430_870_688
    m = cfg["model"]
    assert m["n_layer"] == 16 and len(cfg["layer_types"]) == 16
    for key, value in (("hidden_size", 4096), ("intermediate_size", 8192),
                       ("linear_value_head_dim", 128), ("linear_allow_neg_eigval", False)):
        assert FAM.check_config({**cfg, key: value}), key
    for key, value in (("gdn_beta_scale", 1.0), ("block_norm", "pre"), ("attn_gate", True),
                       ("partial_rotary_factor", 0.25)):
        assert FAM.check_config({**cfg, "model": {**m, key: value}}), key
    assert FAM.check_config({**cfg, "reduced": cfg["reduced"][1:]})
    assert FAM.check_config({**cfg, "layer_types": cfg["layer_types"][::-1]})
    # 12 layers' S (30 x 96 x 192) and conv state (3 x 11,520), float32,
    # read and written once a live row and step.
    assert FAM.state_step_bytes(m, 1) == 2 * 12 * (30 * 96 * 192 + 3 * 11520) * 4
    assert FAM.ffn_step_bytes(m, 1) == 16 * 3 * 3840 * 11008 * 2
    assert FAM.ffn_step_flops(m, 48) == 2 * 48 * 16 * 3 * 3840 * 11008
