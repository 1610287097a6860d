"""SDAR (``tpuflow/models/sdar.py``) and the engine's block-diffusion
generation (``ServeEngine(generation=...)``) against the plain reference in
``benchmark/families/sdar.py``, on seeded weights at the family's test
width, float32 (ISSUE 37).

Tolerances. Program and reference are both float32 here and differ by the
order of their sums alone: a logit agrees to 2e-5 (largest read 3e-6, logits
up to 3.5), and a served token lies no further below the reference's best
than 1e-4 (read: 0 on every token: it *is* the reference's argmax). The
same comparisons against the reference computed with bfloat16 or float8
products (``harness/reference.py`` ``QUANT``) read 1e-2 to 5e-1: the last
tests show that such a substitution fails them.
"""

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
from tpuflow.infer import serve  # noqa: E402
from tpuflow.infer.serve import GenerationUnsupported, ServeEngine  # noqa: E402

LOGIT_TOL = 2e-5  # float32 against float32: the order of the sums
GAP_TOL = 1e-4
PAGE = 8
FAM = manifest.load_family("sdar")
M = FAM.test_config()["model"]
L = M["block_length"]


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: FAM.make_params(M, k))(seed_key(7))


REF_LEN = 64  # the reference's one compiled length (a block-aligned prefix is exact)


@jax.jit
def _ref_logits(params, ids, masked):
    return FAM.forward_logits(params, ids, masked, M)


def ref_logits(params, ids, masked=None):
    """The reference's logits of one sequence, computed at `REF_LEN`: what
    lies after a whole block changes nothing before it."""
    n = len(ids)
    assert n % L == 0 and n <= REF_LEN
    pad = np.zeros(REF_LEN - n, np.int64)
    masked = np.zeros(n, bool) if masked is None else masked
    return np.asarray(_ref_logits(
        params, jnp.asarray(np.r_[ids, pad]), jnp.asarray(np.r_[masked, pad > 0])
    ))[:n]


def _engine(params, steps, unmask, **kw):
    m = dict(M, denoise_steps=steps)
    kw = {"max_slots": 4, "buckets": [48], "page_size": PAGE, **kw}
    return m, ServeEngine(
        FAM.module(m), params,
        generation={"kind": "block_diffusion", "denoise_steps": steps, "unmask": unmask},
        **kw,
    )


@pytest.fixture(scope="module")
def engines(params):
    """One engine for each (denoise steps, order) the tests serve with,
    built when first asked for and shared."""
    made = {}

    def get(steps, unmask):
        if (steps, unmask) not in made:
            made[steps, unmask] = _engine(params, steps, unmask)
        return made[steps, unmask]

    return get


@pytest.fixture(scope="module")
def small(params):
    """A two-slot engine without a prefix cache (S = 2, `sequential`)."""
    return _engine(params, 2, "sequential", max_slots=2, prefix_cache=False)[1]


def _prompts(rng, lengths):
    return [rng.integers(1, 250, size=n).astype(np.int32) for n in lengths]


def replay(params, m, prompt, tokens, passes, unmask):
    """Block by block, pass by pass, the plain forward of the partly masked
    sequence (no joined copies): per served token the gap by which it lies
    below the reference's best at its position in the pass that unmasked
    it, and whether every pass unmasked the positions its order names."""
    Lb, per = m["block_length"], m["block_length"] // m["denoise_steps"]
    seq = np.concatenate([prompt, tokens]).astype(np.int64)
    start, end = prompt.size // Lb * Lb, prompt.size + len(tokens)
    when = np.concatenate([np.full(prompt.size, -1), np.asarray(passes)])
    gaps, order_ok = np.zeros(len(tokens)), True
    for c in range(start, end, Lb):
        ids = np.zeros(c + Lb, np.int64)
        ids[: min(end, c + Lb)] = seq[: c + Lb]
        at = np.arange(c, c + Lb)
        emitted = at < end
        for s in range(m["denoise_steps"]):
            known = emitted & (when[np.minimum(at, end - 1)] < s)
            now = emitted & (when[np.minimum(at, end - 1)] == s)
            masked = np.zeros(c + Lb, bool)
            masked[c:] = ~known
            logits = ref_logits(params, ids, masked)[c:]
            for j in np.nonzero(now)[0]:
                gaps[c + j - prompt.size] = logits[j].max() - logits[j, seq[c + j]]
            eligible = ~known & emitted
            if unmask == "sequential":
                want = np.nonzero(eligible)[0][:per]
            else:
                z = logits - logits.max(-1, keepdims=True)
                conf = np.where(eligible, 1.0 / np.exp(z).sum(-1), -1.0)
                want = np.sort(np.argsort(-conf, kind="stable")[: min(per, eligible.sum())])
            order_ok &= np.array_equal(want, np.nonzero(now)[0])
    return gaps, order_ok


# --------------------------------------------- (a) the module's full forward
@pytest.mark.parametrize("length", [4, 13, 22])
def test_full_forward_under_the_block_causal_mask(params, length):
    rng = np.random.default_rng(length)
    toks = rng.integers(0, 256, size=(2, length)).astype(np.int32)
    masked = rng.random((2, length)) < 0.3
    ids = np.where(masked, M["mask_id"], toks)
    mine = FAM.module(M).apply({"params": params}, jnp.asarray(ids))
    ref = jax.jit(lambda p, t, k: FAM.forward_logits(p, t, k, M))(params, toks, masked)
    np.testing.assert_allclose(mine, ref, atol=LOGIT_TOL, rtol=0)


def test_the_mask_is_block_causal_and_no_wider(params):
    """A later block's token changes nothing before it; a token of the
    same block changes the logits of the block's earlier positions."""
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 250, size=(1, 12)).astype(np.int32)
    model = FAM.module(M)
    base = np.asarray(model.apply({"params": params}, jnp.asarray(toks)))
    other = toks.copy()
    other[0, 7] += 1  # the last position of block 1
    got = np.asarray(model.apply({"params": params}, jnp.asarray(other)))
    np.testing.assert_array_equal(got[0, :4], base[0, :4])
    assert np.abs(got[0, 4:7] - base[0, 4:7]).max() > 1e-3
    assert np.abs(got[0, 8:] - base[0, 8:]).max() > 1e-3


# ------------------- (b) prefill, denoise and commit through the paged pool
@pytest.mark.parametrize("steps,unmask", [
    (1, "sequential"), (2, "sequential"), (4, "sequential"),
    (2, "low_confidence"), (4, "low_confidence"),
])
def test_served_tokens_are_the_references_at_every_pass(params, engines, steps, unmask):
    """Prompts of every length mod 4, budgets that end inside a block and
    on its edge, several blocks each: every served token is the argmax of
    the reference's forward of the partly masked sequence at the pass that
    unmasked it, and every pass unmasked what its order names."""
    m, eng = engines(steps, unmask)
    rng = np.random.default_rng(steps)
    prompts = _prompts(rng, (8, 5, 14, 3, 23, 40))
    budgets = (7, 9, 3, 12, 16, 10)
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
    eng.run_until_idle(max_iters=200)
    for p, n, r in zip(prompts, budgets, reqs):
        assert r.done and r.finish_reason == "budget" and len(r.tokens) == n
        assert len(r.token_passes) == n and set(r.token_passes) <= set(range(steps))
        gaps, order_ok = replay(params, m, p, np.asarray(r.tokens), r.token_passes, unmask)
        assert gaps.max() <= GAP_TOL and order_ok
    assert eng.pool.allocated_pages == 0


def test_the_joined_replay_agrees_with_block_by_block(params, engines):
    """`token_gaps` evaluates all blocks of a pass index in one forward over
    the clean sequence joined to its masked copies; `replay` above runs
    every block's every pass as a forward of its own. On served tokens with
    some altered (so that the gaps are not nought) the two read the same."""
    m, eng = engines(2, "sequential")
    rng = np.random.default_rng(11)
    prompts = _prompts(rng, (6, 9, 31))
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, (10, 5, 8))]
    eng.run_until_idle(max_iters=100)
    samples = []
    for p, r in zip(prompts, reqs):
        toks = np.asarray(r.tokens)
        toks[2] = (toks[2] + 5) % 250
        samples.append((p, toks))
    joined = FAM.token_gaps(m, 7, samples)
    for (p, toks), got in zip(samples, joined):
        passes = FAM.replay_plan(p.size, toks.size, m)["pass_of"]
        want, _ = replay(params, m, p, toks, passes[passes >= 0], "sequential")
        np.testing.assert_allclose(got["gap"], want, atol=GAP_TOL, rtol=0)
        assert got["gap"].max() > 10 * GAP_TOL and got["margin"].shape == want.shape
    assert FAM.serve_gaps(m, 7, samples)["widest_gap"] > 10 * GAP_TOL
    clean = [(p, np.asarray(r.tokens)) for p, r in zip(prompts, reqs)]
    assert FAM.serve_gaps(m, 7, clean)["widest_gap"] <= GAP_TOL


# ------------------------- (c) one pass's logits through the pool, directly
@pytest.mark.parametrize("left", [0, 1, 2, 3])
def test_a_pass_over_the_pool_reads_the_references_logits(params, engines, left):
    """Admit a prompt of 16 + `left` tokens (its whole blocks are prefilled
    into pages, the rest opens the first block), then run the paged model
    by hand over that block with one more position unmasked at a time, as
    S = 4 `sequential` passes do: the logits of all four positions equal
    the reference's forward of the same partly masked sequence."""
    m, eng = engines(4, "sequential")
    rng = np.random.default_rng(left)
    prompt = _prompts(rng, (16 + left,))[0]
    req = eng.submit(prompt, max_new_tokens=8)
    with obs.span("serve.admit", request=req.id) as sp:
        assert eng._admit_one(eng._queue.popleft(), 0, sp)
    assert eng._lengths[0] == 16 and (eng._tok[0] >= 0).sum() == left
    fill = rng.integers(1, 250, size=L)
    for known in range(left, L):
        block = np.where(np.arange(L) < known, np.r_[prompt[16:], fill][:L], -1)
        ids = np.where(block < 0, m["mask_id"], block)[None]
        logits, _ = eng._pmodel.apply(
            {"params": params, "cache": eng._cache}, jnp.asarray(ids, jnp.int32),
            decode=True, mutable=["cache", "step_sum", "step_max"],
            pad_lens=jnp.zeros((1,), jnp.int32), slot_index=jnp.asarray([16]),
            page_table=jnp.asarray(eng._page_table[:1]),
        )
        seq = np.r_[prompt[:16], np.maximum(block, 0)]
        ref = ref_logits(params, seq, np.r_[np.zeros(16, bool), block < 0])
        np.testing.assert_allclose(logits[0], ref[16:], atol=LOGIT_TOL, rtol=0)
    eng.run_until_idle(max_iters=20)  # the request runs out; the slot is free again
    assert req.done


# -------------------- (d) rows at different phases, sharing pages, or alone
def test_requests_together_generate_what_each_generates_alone(params, engines, small):
    """Six requests through four slots: rows whose first blocks open with
    0 to 3 prompt tokens run in one program call, rows finish inside a call
    and inside a block, later ones are admitted beside rows mid-way, and
    three share a 16-token system prompt (two pages of the prefix cache).
    Each generates what it generates served alone on a fresh engine."""
    m, eng = engines(2, "sequential")
    rng = np.random.default_rng(3)
    system = _prompts(rng, (16,))[0]
    prompts = _prompts(rng, (5, 10, 7))
    prompts += [np.concatenate([system, t]) for t in _prompts(rng, (3, 6, 1))]
    budgets = (9, 2, 17, 6, 11, 5)
    hits = eng.pool.prefix_hits
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts[:5], budgets)]
    eng.step()
    reqs.append(eng.submit(prompts[5], max_new_tokens=budgets[5]))
    eng.run_until_idle(max_iters=200)
    assert eng.pool.prefix_hits >= hits + 4  # two pages, for two later requests
    for p, n, r in zip(prompts, budgets, reqs):
        solo = small.submit(p, max_new_tokens=n)
        small.run_until_idle(max_iters=100)
        assert r.tokens == solo.tokens and len(r.tokens) == n


def test_nothing_compiles_after_warmup(small):
    eng = small
    base = eng.warmup()
    assert base["decode"] == len(eng.decode_shapes)
    rng = np.random.default_rng(4)
    reqs = [eng.submit(p, max_new_tokens=6) for p in _prompts(rng, (3, 9, 12))]
    eng.run_until_idle(max_iters=100)
    assert all(r.done for r in reqs) and eng.compile_stats() == base


# -------------------------------------- (e) what the engine counts adds up
def test_tokens_passes_and_lengths_add_up_over_a_run(small, tmp_path):
    """Over a whole run: tokens a call is the `remaining` delta; a row's
    committed length stays a multiple of the block; the `serve.decode`
    spans carry `tokens`, `passes`, `commit_passes`, `slots`, `rows`,
    `pages` and the model's counts, and sum to the ledger's totals;
    `tokens_per_pass` is tokens over live rows x passes, 4 / 3 at the most;
    `serve.first_token` fires once a request, at its first harvest."""
    obs.configure(str(tmp_path))
    try:
        eng = small
        eng.ledger.reset()
        assert eng.decode_block == 6 and eng._advance == 8
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, (4, 9, 18, 7, 12))
        budgets = (12, 5, 8, 16, 1)
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, budgets)]
        while eng._queue or eng._live.any():
            before, emitted = eng._remaining.copy(), eng._emitted_tokens
            eng.step()
            gone = np.where(eng._live, before - eng._remaining, 0).sum()
            assert gone <= eng._emitted_tokens - emitted
            assert (eng._lengths % L == 0).all()
            assert (eng._tok[eng._live] == -1).all()  # a call ends at a block's edge
        snap = eng.ledger.snapshot()
    finally:
        obs.configure(None)
    import glob
    import json

    events = []
    for path in glob.glob(os.path.join(str(tmp_path), "*.jsonl")):
        with open(path) as f:
            events += [json.loads(line) for line in f if line.strip()]
    blocks = [e for e in events if e.get("kind") == "span" and e.get("name") == "serve.decode"]
    assert blocks and all(
        {"tokens", "passes", "commit_passes", "slots", "rows", "pages",
         "experts_touched", "expert_max_load"} <= set(e) for e in blocks
    )
    assert all(e["passes"] == 6 and e["commit_passes"] == 2 for e in blocks)
    assert sum(e["tokens"] for e in blocks) == sum(budgets) == sum(len(r.tokens) for r in reqs)
    assert snap["commit_passes"] == 2 * len(blocks) and snap["denoise_passes"] == 4 * len(blocks)
    assert snap["model_steps"] == 6 * len(blocks)
    assert snap["step_sum"]["experts_touched"] == sum(e["experts_touched"] for e in blocks)
    rate = sum(e["tokens"] for e in blocks) / sum(e["slots"] * e["passes"] for e in blocks)
    assert snap["tokens_per_pass"] == pytest.approx(rate) and 0 < rate <= L / 3
    gauges = [e for e in events if e.get("name") == "serve.tokens_per_pass"]
    assert gauges and gauges[-1]["value"] == pytest.approx(rate, abs=1e-4)
    first = [e["request"] for e in events if e.get("name") == "serve.first_token"]
    assert sorted(first) == [r.id for r in reqs]
    for r in reqs:
        assert r.t_first is not None and r.t_admit <= r.t_first <= r.t_done


def test_a_full_call_of_full_blocks_yields_four_tokens_in_three_passes(small):
    eng = small
    eng.ledger.reset()
    rng = np.random.default_rng(6)
    for p in _prompts(rng, (8, 12)):
        eng.submit(p, max_new_tokens=8)  # whole blocks, both rows live to the end
    eng.run_until_idle(max_iters=10)
    assert eng.ledger.tokens_per_pass == pytest.approx(L / 3)


# -------------------------------------------- (f) what has no meaning yet
def test_generation_is_validated_and_defaults_to_the_models(params):
    model = FAM.module(M)
    eng = ServeEngine(model, params, max_slots=1, buckets=[16], page_size=PAGE,
                      generation={"kind": "block_diffusion"})
    assert eng.generation == {"kind": "block_diffusion", "block_length": 4, "denoise_steps": 2,
                              "unmask": "sequential", "mask_id": 255}
    assert ServeEngine(model, params, max_slots=1, buckets=[16]).generation is None
    for bad, match in [
        ({"kind": "diffusion"}, "kind"), ({"kind": "block_diffusion", "block_length": 8}, "block_length"),
        ({"kind": "block_diffusion", "denoise_steps": 3}, "denoise_steps"),
        ({"kind": "block_diffusion", "unmask": "random"}, "unmask"),
        ({"kind": "block_diffusion", "mask_id": 256}, "mask_id"),
        ({"kind": "block_diffusion", "threshold": 0.9}, "unknown"),
    ]:
        with pytest.raises(ValueError, match=match):
            serve.resolve_generation(bad, model)
    with pytest.raises(ValueError, match="multiple of the generation's block_length"):
        ServeEngine(model, params, max_slots=1, buckets=[16], page_size=2,
                    generation={"kind": "block_diffusion"})
    with pytest.raises(ValueError, match="multiple of denoise_steps"):
        ServeEngine(model, params, max_slots=1, buckets=[16], page_size=PAGE, decode_block=4,
                    generation={"kind": "block_diffusion"})


@pytest.mark.parametrize("option", ["speculative", "quant", "kv_store_dir", "kv_host_mb"])
def test_an_engine_option_without_meaning_under_generation_raises(params, tmp_path, option):
    value = {"speculative": 2, "quant": "weight_only", "kv_store_dir": str(tmp_path),
             "kv_host_mb": 1.0}[option]
    with pytest.raises(GenerationUnsupported, match="generation='block_diffusion'"):
        ServeEngine(FAM.module(M), params, max_slots=1, buckets=[16], page_size=PAGE,
                    generation={"kind": "block_diffusion"}, **{option: value})


def test_a_request_option_without_meaning_under_generation_raises(params, engines):
    _, eng = engines(2, "sequential")
    prompt = np.arange(1, 9, dtype=np.int32)
    with pytest.raises(GenerationUnsupported, match="eos_id"):
        eng.submit(prompt, max_new_tokens=4, eos_id=3)
    with pytest.raises(GenerationUnsupported, match="prefill_export"):
        eng.prefill_export(prompt)
    with pytest.raises(GenerationUnsupported, match="prefill_export"):
        eng.ship(prompt, store=object())
    with pytest.raises(ValueError, match="quant-armed"):
        eng.submit(prompt, max_new_tokens=4, quantize=True)
    with pytest.raises(ValueError, match="spec-armed"):
        eng.submit(prompt, max_new_tokens=4, speculative=True)
    assert not eng._queue


# ----------------------------- a lower precision in the reference's place
@pytest.mark.parametrize("quant", ["bf16", "fp8"])
def test_a_lower_precision_fails_the_tolerances(params, engines, quant):
    rng = np.random.default_rng(8)
    toks = rng.integers(0, 250, size=(1, 22)).astype(np.int32)
    mine = FAM.module(M).apply({"params": params}, jnp.asarray(toks))
    low = jax.jit(lambda p, t: FAM.forward_logits(p, t, None, M, quant))(params, toks)
    assert float(jnp.max(jnp.abs(mine - low))) > 100 * LOGIT_TOL
    m, eng = engines(2, "sequential")
    prompts = _prompts(rng, (6, 21, 33, 12))
    reqs = [eng.submit(p, max_new_tokens=24) for p in prompts]
    eng.run_until_idle(max_iters=100)
    samples = [(p, np.asarray(r.tokens)) for p, r in zip(prompts, reqs)]
    got = FAM.serve_gaps(m, 7, samples, quant=quant)
    assert got["tokens"] == 96 and got["widest_gap"] <= GAP_TOL
    assert got["widest_gap_low"] > 10 * GAP_TOL
