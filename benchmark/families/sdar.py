"""The SDAR family (JetLM, `model_type: sdar_moe`): the only file of the
benchmark that knows this architecture. A configuration's file names it
(`"family": "sdar"`), and `manifest.load_family` finds it by that name.

It holds the program's module built from a configuration's `model` group,
the weights from `--seed`, the plain reference, and the operations and bytes
worked out from shapes. The family trains in no cell: no training functions.

The plain reference is the forward pass of the equations in
`tpuflow/models/sdar.py`'s docstring in straightforward `jax.numpy`, float32
at `highest` matmul precision: one sequence, an explicit mask (a (T, T)
table of who sees whom), no cache, no pages, no kernels, the routed experts
as a dense sum over all of them, each weighted by what the router gave the
token (nought where it was not chosen). It takes nothing the program has
made but the weights' recipe. Weights: bfloat16, every layer keyed by its
index, so that the program makes the whole tree in one jitted call
(`make_params`, the stack by `lax.map`) and the reference makes one layer at
a time inside its layer loop and drops it (`token_gaps`): 4.98 B parameters
do not fit one chip in float32.

**What `correct` replays.** The served model generates by diffusion over
blocks (`ServeEngine._denoise_fn`), so a served token is not the next-token
argmax of a teacher-forced pass. `token_gaps` replays the `sequential`
order from the prompt and the served tokens alone: block b's pass s ran on
the prompt, the served tokens before the block, the block's positions
unmasked so far and the mask id at the others; the gap of a token is the
reference's best logit, at its position in the pass that unmasked it, less
its logit of the served token there. All blocks of one pass index are
evaluated in one forward over the clean sequence joined to its partly masked
copies (the form the model is trained in): a copy's block sees the clean
blocks before it and itself, the clean sequence sees itself block-causally.
"""

from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import manifest
from benchmark.harness import reference as R
from benchmark.harness.runner import log

# The scopes inside a layer and after it: what `decode_carry_share.serve`
# leaves out, and the labels of the tables (PERF.md section 3). `unmask` is
# the engine's, inside a denoise pass.
BLOCK_SCOPES = (
    "attn_q", "kv_write", "kv_read", "attn_core", "attn_out", "router",
    "moe_experts", "lm_head", "unmask",
)
MODULE_SCOPES = ("layer", "layers", "Sdar")  # flax's own, around everything

# The published widths (the configuration's top-level keys) and the key of
# the `model` group each has to equal.
PUBLISHED = {
    "hidden_size": "hidden_size", "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head", "head_dim": "head_dim",
    "moe_intermediate_size": "moe_intermediate_size", "num_experts": "n_experts",
    "num_experts_per_tok": "n_experts_per_tok", "norm_topk_prob": "norm_topk_prob",
    "vocab_size": "vocab_size", "rms_norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
}
SERVED = {"num_hidden_layers": "n_layer", "max_position_embeddings": "n_ctx"}


# ------------------------------------------------------------ the program
def module(m: dict):
    """The program's flax module for a configuration's `model` group. A
    program from before the model was written cannot run the family's
    cells: it is told so at once, before anything is built."""
    try:
        from tpuflow.models.sdar import Sdar, SdarConfig
    except ImportError as e:
        raise manifest.ManifestError(
            f"family 'sdar' needs tpuflow/models/sdar.py, which this program lacks ({e})"
        ) from e

    fields = dict(m)
    fields["dtype"] = jnp.dtype(fields.get("dtype", "bfloat16"))
    return Sdar(SdarConfig(**fields))


def positions(m: dict) -> int:
    """The longest sequence a request may reach."""
    return m["n_ctx"]


def vocabulary(m: dict) -> int:
    """The ids the traffic may draw (the mask id among them: the engine
    knows a masked position from its own state, not from the id)."""
    return m["vocab_size"]


def check_config(cfg: dict) -> list[str]:
    """Problems with a configuration's file (empty = none): every width as
    published, the depth and the positions the file says it reduced to,
    the parameter count the file states, a dense width the model never
    uses, and a `serve.generation` that is the model's own."""
    bad = []
    m = cfg["model"]
    for key, mine in PUBLISHED.items():
        if m[mine] != cfg[key]:
            bad.append(f"model.{mine} {m[mine]} is not the published {key} {cfg[key]}")
    if cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        bad.append("a dense layer in the pattern: the model has none (intermediate_size unused)")
    served = cfg["served"]
    for key, mine in SERVED.items():
        if m[mine] != served[key]:
            bad.append(f"model.{mine} {m[mine]} is not served.{key} {served[key]}")
        if key not in cfg["reduced"] and served[key] != cfg[key]:
            bad.append(f"served.{key} differs from the source and is not in reduced")
    if n_params(m) != cfg["parameters"]:
        bad.append(f"parameters {cfg['parameters']} is not the {n_params(m)} of the shapes")
    gen = cfg["serve"].get("generation") or {}
    for key in ("block_length", "denoise_steps", "mask_id"):
        if gen.get(key, m[key]) != m[key]:
            bad.append(f"serve.generation.{key} {gen[key]} is not model.{key} {m[key]}")
    if gen.get("kind") != "block_diffusion":
        bad.append("serve.generation.kind is not block_diffusion")
    return bad


def test_config() -> dict:
    """A configuration at the `test` width for the CPU tests, with the
    limit of `correct` at that size (float32 program against the float32
    reference: rounding alone, 1e-4 of a logit; the float8 control reads
    0.02 to 0.2 there)."""
    return {
        "model": {
            "vocab_size": 256, "n_ctx": 128, "hidden_size": 64, "n_layer": 3, "n_head": 4,
            "n_kv_head": 2, "head_dim": 16, "moe_intermediate_size": 32, "n_experts": 8,
            "n_experts_per_tok": 2, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
            "rope_theta": 1000000.0, "block_length": 4, "denoise_steps": 2, "mask_id": 255,
            "dtype": "float32",
        },
        "serve": {"max_slots": 4, "paged": True, "prefix_cache": True, "speculative": 0,
                  "quant": None,
                  "generation": {"kind": "block_diffusion", "block_length": 4,
                                 "denoise_steps": 2, "unmask": "sequential", "mask_id": 255}},
        "limits": {"serve": {"widest_logit_gap": 1e-3}},
    }


# ------------------------------- operations and bytes worked out from shapes
ROUTED = ("experts_gate_up", "experts_down")


def _layer_shapes(m: dict) -> dict[str, tuple[int, ...]]:
    """Every leaf of one layer, by the name the program's module gives it."""
    c, h, g, d = m["hidden_size"], m["n_head"], m["n_kv_head"], m["head_dim"]
    e, f = m["n_experts"], m["moe_intermediate_size"]
    return {
        "attn_norm": (c,), "q": (c, h * d), "k": (c, g * d), "v": (c, g * d),
        "q_norm": (d,), "k_norm": (d,), "o": (h * d, c), "mlp_norm": (c,),
        "router": (c, e), "experts_gate_up": (e, c, 2 * f), "experts_down": (e, f, c),
    }


def _count(shapes: dict, skip=()) -> int:
    return sum(math.prod(s) for k, s in shapes.items() if k not in skip)


def n_params(m: dict) -> int:
    """Parameters held: embedding, untied head, final norm, every layer
    with every expert."""
    c, v = m["hidden_size"], m["vocab_size"]
    return 2 * v * c + c + m["n_layer"] * _count(_layer_shapes(m))


def _routed_expert_params(m: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _layer_resident_params(m: dict) -> int:
    """What every pass reads of a layer whatever is routed."""
    return _count(_layer_shapes(m), skip=ROUTED)


def _head_params(m: dict) -> int:
    return m["vocab_size"] * m["hidden_size"] + m["hidden_size"]


def routed_experts_held(m: dict) -> int:
    """Routed experts over all layers: the most a pass can touch."""
    return m["n_layer"] * m["n_experts"]


def forward_flops_per_token(m: dict) -> float:
    """2 x the parameters one position uses in one forward pass: everything
    resident and `n_experts_per_tok` routed experts a layer. `mfu.serve`
    multiplies it by the tokens delivered, one position-forward each, so
    what denoising spends beyond that (a block's S + 1 passes over all L of
    its positions) reads as a lower share, not as work done. The
    attention-score products are not counted."""
    used = _layer_resident_params(m) + m["n_experts_per_tok"] * _routed_expert_params(m)
    return 2.0 * (_head_params(m) + m["n_layer"] * used)


def train_flops_per_token(m: dict) -> float:
    """Forward and backward, three times the forward (no cell trains this
    family: `mfu.train` would ask)."""
    return 3.0 * forward_flops_per_token(m)


def attention_flops(m: dict, batch: int, seq: int, steps: float) -> float:
    """The least block-causal attention has to do in `steps` training steps
    (scores and values over `head_dim`, half under the mask, three times
    with the backward pass)."""
    per_layer = 3 * 0.5 * 2.0 * batch * m["n_head"] * seq * seq * 2 * m["head_dim"]
    return steps * m["n_layer"] * per_layer


def moe_step_bytes(m: dict, experts_touched: float, weight_bytes: int = 2) -> float:
    """Bytes the routed-expert products have to read for `experts_touched`
    (distinct experts, summed over layers and passes): each one's three
    matrices once."""
    return float(experts_touched * _routed_expert_params(m) * weight_bytes)


def decode_step_bytes(m: dict, live_context_tokens: int, weight_bytes: int = 2,
                      cache_bytes: int = 2) -> float:
    """The least one forward pass of a decode call has to read: every
    layer's resident weights once, the routed experts one live row's one
    position touches (`n_experts_per_tok` a layer: the reader is not told
    how many rows are live, and every further position only adds), the keys
    and values of the live context once, and the head in the S of a block's
    S + 1 passes that compute one (a commit pass reads none)."""
    s = m["denoise_steps"]
    kv = 2 * m["n_kv_head"] * m["head_dim"]
    return float(
        (m["n_layer"] * _layer_resident_params(m) + _head_params(m) * s / (s + 1)) * weight_bytes
        + moe_step_bytes(m, m["n_layer"] * m["n_experts_per_tok"], weight_bytes)
        + m["n_layer"] * live_context_tokens * kv * cache_bytes
    )


# -------------------------------------------------- weights from `--seed`
PUBLISHED_HIDDEN = 2048
TOP_KEY = 1_000_000  # a fold-in beside the layers' indices


def _leaf(key, name: str, shape, m: dict):
    """One leaf, bfloat16-rounded values in the model's dtype. Sizes: 0.02
    for matrices; the embedding 1 (Xing4's reasoning: a stream that is only
    its last sub-layers' outputs is one that rounding throws about; PERF.md
    section 6, PR 37 reads it again: at 0.1 a quarter of the served tokens
    lose their place to bfloat16 alone); **the attention's out-projection
    0.3 / sqrt(2 L)**, fifteen times the usual, so that what attention
    brings a position, an average over its row's whole context and so a
    steady thing, weighs about one and a half times its own token's
    embedding: the routers then choose by the row's context as much as by
    the token. With the usual 0.02 every masked position of every request,
    all entering as one embedding, chose the same experts and unmasked the
    same token, and how many other clusters of positions a pass held hung on
    the seed's weights (18 to 34 of 128 experts a pass, a tail that followed
    the seed by 30%); at 0.6 the served tokens no longer noticed a causal
    mask in the block's place or a commit pass left out (the rehearsals
    of `benchmark/tests/test_sdar_family.py`), at 0.3 they do (the same
    section). The experts' down-projection 0.1 / sqrt(2 L), because a token's
    eight experts enter with weights of about an eighth; norms around 1."""
    z = jax.random.normal(key, shape, jnp.float32)
    # Matrices are sized for the published width; a narrower model (the
    # tests') gets them larger by the root of the ratio, so that its
    # sub-layers weigh as much against its stream as the cell's do.
    wide = math.sqrt(PUBLISHED_HIDDEN / m["hidden_size"])
    if "norm" in name:
        val = 1.0 + 0.02 * z
    elif name == "o":
        val = 0.3 * wide / math.sqrt(2 * m["n_layer"]) * z
    elif name == "experts_down":
        val = 0.1 * wide / math.sqrt(2 * m["n_layer"]) * z
    elif name == "embed":
        val = z
    else:
        val = 0.02 * wide * z
    return val.astype(jnp.bfloat16).astype(jnp.dtype(m.get("dtype", "bfloat16")))


def layer_params(m: dict, key, index) -> dict:
    """Layer `index`'s leaves (call under `jit`; `index` may be traced)."""
    k = jax.random.fold_in(key, index)
    return {
        name: _leaf(jax.random.fold_in(k, j), name, shape, m)
        for j, (name, shape) in enumerate(sorted(_layer_shapes(m).items()))
    }


def top_params(m: dict, key) -> dict:
    c, v = m["hidden_size"], m["vocab_size"]
    k = jax.random.fold_in(key, TOP_KEY)
    shapes = {"embed": (v, c), "norm_f": (c,), "lm_head": (c, v)}
    return {
        name: _leaf(jax.random.fold_in(k, j), name, shape, m)
        for j, (name, shape) in enumerate(sorted(shapes.items()))
    }


def make_params(m: dict, key) -> dict:
    """The whole parameter tree (call under `jit`), as the program's module
    declares it: the layers stacked, their experts in leaves of their own
    beside the stack."""
    tree = top_params(m, key)
    stack = lax.map(lambda i: layer_params(m, key, i), jnp.arange(m["n_layer"]))
    for name in ROUTED:
        tree[name] = stack.pop(name)
    tree["layers"] = {"layer": stack}
    return tree


def layer_of(params, i: int) -> dict:
    """Layer `i`'s leaves out of a whole tree, experts included."""
    lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"]["layer"])
    return {**lp, **{name: params[name][i] for name in ROUTED}}


def leaf_norms(tree, m: dict, *, minus_key=None, scale: float = 1.0) -> dict[str, float]:
    """The norm of every leaf (times `scale`); with `minus_key`, of the leaf
    less the initial leaf that key makes."""
    if minus_key is not None:
        first = jax.jit(lambda k: make_params(m, k))(minus_key)
        tree = jax.tree_util.tree_map(lambda a, b: a - b, tree, first)
    return {
        jax.tree_util.keystr(path): float(jnp.linalg.norm(leaf.astype(jnp.float32).ravel())) * scale
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def delta_norms(params, m: dict, seed: int) -> dict[str, float]:
    """Per leaf, the norm of (params - the seed's initial leaf)."""
    return leaf_norms(params, m, minus_key=R.seed_key(seed))


def train_reference(m: dict, opt: dict, seed: int, batches, **_) -> dict:
    """No cell trains this family (training under the masked-block loss is
    left for a later PR: ROADMAP.md, M9), so no reference of a training step
    was written: say so where one is asked for."""
    raise NotImplementedError(
        "the sdar family trains in no cell: its reference is the forward pass alone"
    )


# ---------------------------------------------------- the plain reference
def _mm(a, b, q):
    return jnp.matmul(q(a), q(b), precision=R.HIGHEST)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """Rotate the halves of (T, H, D) by the angles of positions `pos` (T,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1
    )


def _attention(x, lp, m: dict, pos, visible, q):
    t = x.shape[0]
    h, g, d, eps = m["n_head"], m["n_kv_head"], m["head_dim"], m["rms_norm_eps"]
    qh = _rope(_rms(_mm(x, lp["q"], q).reshape(t, h, d), lp["q_norm"], eps), pos, m["rope_theta"])
    kh = _rope(_rms(_mm(x, lp["k"], q).reshape(t, g, d), lp["k_norm"], eps), pos, m["rope_theta"])
    vh = _mm(x, lp["v"], q).reshape(t, g, d)
    # every query head beside the group whose keys and values it reads
    kh, vh = (jnp.repeat(a, h // g, axis=1) for a in (kh, vh))
    s = jnp.einsum("qhd,khd->hqk", q(qh), q(kh), precision=R.HIGHEST) * d ** -0.5
    p = jax.nn.softmax(jnp.where(visible[None], s, -1e30), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", q(p), q(vh), precision=R.HIGHEST)
    return _mm(a.reshape(t, h * d), lp["o"], q)


def router_logits(x, lp, q=R.QUANT[None]):
    return _mm(x, lp["router"], q)


def router(x, lp, m: dict, q=R.QUANT[None]):
    """(T, E) weights: nought for an expert the token did not choose."""
    p = jax.nn.softmax(router_logits(x, lp, q), axis=-1)
    _, idx = lax.top_k(p, m["n_experts_per_tok"])
    w = p * jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)
    return w / jnp.sum(w, axis=-1, keepdims=True) if m["norm_topk_prob"] else w


def router_margin(x, lp, m: dict):
    """By how much of a router logit each token's last chosen expert leads
    the first one left out: what rounding has to move to flip the choice."""
    top, _ = lax.top_k(router_logits(x, lp), m["n_experts_per_tok"] + 1)
    return top[:, -2] - top[:, -1]


def _moe(x, lp, m: dict, q):
    w = router(x, lp, m, q)

    def one(y, ew):
        gate_up, down, we = (a.astype(jnp.float32) for a in ew)
        g, u = jnp.split(_mm(x, gate_up, q), 2, axis=-1)
        return y + we[:, None] * _mm(jax.nn.silu(g) * u, down, q), None

    y, _ = lax.scan(one, jnp.zeros_like(x), (lp["experts_gate_up"], lp["experts_down"], w.T))
    return y


def layer(x, lp, m: dict, pos, visible, quant=None, margins: bool = False):
    """One layer on the stream x (T, C) of one sequence, float32, whose
    tokens lie at positions `pos` (T,) and see each other as `visible`
    (T, T) says; with `margins`, (x, each token's `router_margin`)."""
    q = R.QUANT[quant]
    experts = {name: lp[name] for name in ROUTED}  # cast one expert at a time
    lp = {k: v.astype(jnp.float32) for k, v in lp.items() if k not in ROUTED} | experts
    eps = m["rms_norm_eps"]
    h = x + _attention(_rms(x, lp["attn_norm"], eps), lp, m, pos, visible, q)
    hn = _rms(h, lp["mlp_norm"], eps)
    y = h + _moe(hn, lp, m, q)
    return (y, router_margin(hn, lp, m)) if margins else y


def head(top, h, m: dict, quant=None):
    """Logits of the stream h (T, C)."""
    top = {k: top[k].astype(jnp.float32) for k in ("norm_f", "lm_head")}
    return _mm(_rms(h, top["norm_f"], m["rms_norm_eps"]), top["lm_head"], R.QUANT[quant])


def block_causal(pos, block_length: int):
    """(T, T): position j is visible to position i iff its block is not later."""
    return (pos[None, :] // block_length) <= (pos[:, None] // block_length)


def forward_logits(params, tokens, masked, m: dict, quant=None):
    """(T,) token ids of one sequence -> (T, V) logits, or (R, T) -> (R, T,
    V), from a whole parameter tree (the tests' size), under the block-causal
    mask; `masked` (the tokens' shape, bool, or None) marks the positions
    whose input is the mask id's embedding."""
    tokens = jnp.asarray(tokens)
    if masked is not None:
        tokens = jnp.where(jnp.asarray(masked), m["mask_id"], tokens)
    if tokens.ndim == 2:
        return jax.vmap(lambda t: forward_logits(params, t, None, m, quant))(tokens)
    pos = jnp.arange(tokens.shape[0])
    visible = block_causal(pos, m["block_length"])
    x = params["embed"][tokens].astype(jnp.float32)
    for i in range(m["n_layer"]):
        x = layer(x, layer_of(params, i), m, pos, visible, quant)
    return head(params, x, m, quant)


# ------------------------------------------------------ the denoise replay
REFERENCE_PAD = 512  # clean sequences are padded to one multiple: one shape compiles
COPY_PAD = 128       # and the generated region of each masked copy
SERVED_PAD = 128     # and the served positions read of each


def replay_plan(prompt_len: int, served: int, m: dict) -> dict:
    """How the `sequential` order generated `served` tokens after a prompt
    of `prompt_len`, from the two numbers alone. `start`: the first
    generated block's first position (the prompt's whole blocks lie before
    it, its tokens left over open the block). For every generated position
    p, `pass_of[p - start]`: the denoise pass (0 .. S - 1) of its block that
    unmasked it, -1 for a position that was never generated (a prompt token,
    or one beyond the budget); and `known[s][p - start]`: whether pass s ran
    with the position already unmasked. A block's pass unmasks its
    leftmost L / S masked positions that lie inside the budget."""
    L, S = m["block_length"], m["denoise_steps"]
    start = prompt_len // L * L
    end = prompt_len + served
    width = -(-(end - start) // L) * L
    pass_of = np.full((width,), -1, np.int64)
    known = np.zeros((S, width), bool)
    for c in range(0, width, L):
        k = max(prompt_len - (start + c), 0)  # a first block's prompt tokens
        target = min(end - (start + c), L)
        for s in range(S):
            known[s, c:c + k] = True
            nxt = min(k + L // S, target)
            pass_of[c + k:c + nxt] = s
            k = nxt
    return {"start": start, "pass_of": pass_of, "known": known}


def joined(prompt, served, m: dict, length: int, copy: int) -> dict:
    """The clean sequence (padded to `length`) joined to S masked copies of
    its generated region (`copy` positions each, from the plan's `start`):
    token ids, positions and the (N, N) table of who sees whom. Clean
    positions see each other block-causally; a copy's block sees the clean
    blocks strictly before it and itself; nothing sees a copy from outside
    it, and no counted position sees padding. Also `rows`, for each served
    token the row of the joined sequence whose logits unmasked it."""
    L, S = m["block_length"], m["denoise_steps"]
    prompt, served = np.asarray(prompt, np.int64), np.asarray(served, np.int64)
    plan = replay_plan(prompt.size, served.size, m)
    start, width = plan["start"], plan["pass_of"].size
    seq = np.concatenate([prompt, served])
    clean = np.zeros((length,), np.int64)
    clean[: seq.size] = seq
    n = length + S * copy
    ids, pos = np.zeros((n,), np.int64), np.zeros((n,), np.int64)
    ids[:length], pos[:length] = clean, np.arange(length)
    stream = np.zeros((n,), np.int64)  # 0 the clean sequence, 1 + s a copy
    for s in range(S):
        at = slice(length + s * copy, length + (s + 1) * copy)
        # Beyond the generated region a copy runs on, all masked, in blocks
        # of its own that no counted position sees.
        p = start + np.arange(copy)
        known = np.zeros((copy,), bool)
        known[:width] = plan["known"][s]
        ids[at] = np.where(known, clean[np.minimum(p, length - 1)], m["mask_id"])
        pos[at], stream[at] = p, 1 + s
    blk = pos // L
    same = stream[:, None] == stream[None, :]
    from_clean = (stream[:, None] > 0) & (stream[None, :] == 0)
    visible = np.where(
        same, np.where(stream[:, None] == 0, blk[None, :] <= blk[:, None], blk[None, :] == blk[:, None]),
        from_clean & (blk[None, :] < blk[:, None]),
    )
    gen = np.nonzero(plan["pass_of"] >= 0)[0]  # the served tokens, in order
    rows = length + plan["pass_of"][gen] * copy + gen
    assert gen.size == served.size and (start + gen == prompt.size + np.arange(served.size)).all()
    return {"ids": ids, "pos": pos, "visible": visible, "rows": rows}


# A served token whose choice of experts hangs, in some layer of the
# reference, on less of a router logit than FRAGILE_MARGIN is judged apart, by
# the Xing4 family's `judge_tokens` (imported, not copied: its quantile and
# its least robust share are kept). The readings behind the margin: the
# cell's limits file.
FRAGILE_MARGIN = 0.005
_XING4 = manifest.load_family("xing4")  # loaded when first asked, as every family


def judge_tokens(gap, margin):
    """`families/xing4.py` `judge_tokens` on this family's margins: they are
    differences of router logits, its are differences of sigmoid scores, so
    they are brought to its scale (this family's `FRAGILE_MARGIN` lands on
    its own)."""
    scale = _XING4.FRAGILE_MARGIN / FRAGILE_MARGIN
    return _XING4.judge_tokens(gap, np.asarray(margin) * scale)


def token_gaps(m: dict, seed: int, samples, *, quant=None) -> list[dict]:
    """Per sample of (prompt ids, served ids), the denoise replay, an array a
    served token: `gap`, by which its logit lies below the reference's best
    at its position in the pass that unmasked it; `low_gap`, the same of the
    token `quant` puts first there (the control; `gap` again without one);
    `margin`, the least `router_margin` of that row over the layers, and
    `layer`, where that is. One layer's weights are made from the seed, used
    on every sample's stream and dropped. Every sample is padded to the
    longest's lengths, so that each function compiles once."""
    t0 = time.monotonic()
    # The loop has dropped its engine, which its own jitted methods refer
    # back to: only the cycle collector frees it, and this reference needs
    # the room (PERF.md section 6, PR 34).
    gc.collect()
    held = jax.live_arrays()
    log(f"reference starts with {sum(a.nbytes for a in held) / 1e9:.2f} GB in {len(held)} live arrays")
    key = R.seed_key(seed)
    quants = (None,) if quant is None else (None, quant)
    top = jax.jit(lambda k: top_params(m, k))(key)
    samples = [(np.asarray(p, np.int64), np.asarray(t, np.int64)[: positions(m) - len(p)])
               for p, t in samples]
    up = lambda n, unit: -(-max(n, 1) // unit) * unit  # noqa: E731
    length = up(max(len(p) + len(t) for p, t in samples), REFERENCE_PAD)
    copy = up(max(replay_plan(len(p), len(t), m)["pass_of"].size for p, t in samples), COPY_PAD)
    n_rows = up(max(len(t) for _, t in samples), SERVED_PAD)
    plans = [joined(p, t, m, length, copy) for p, t in samples]
    embed = jax.jit(lambda top, ids: top["embed"][ids].astype(jnp.float32))
    streams = {qn: [embed(top, jnp.asarray(pl["ids"])) for pl in plans] for qn in quants}
    n = length + m["denoise_steps"] * copy
    margins = [jnp.full((n,), jnp.inf)] * len(plans)
    tightest = [jnp.zeros((n,), jnp.int32)] * len(plans)  # the layer of each margin
    make = jax.jit(lambda k, i: layer_params(m, k, i))
    step = jax.jit(
        lambda x, lp, pos, visible, qn: layer(x, lp, m, pos, visible, qn, margins=True),
        static_argnums=4, donate_argnums=0,
    )
    where = [(jnp.asarray(pl["pos"]), jnp.asarray(pl["visible"])) for pl in plans]
    for i in range(m["n_layer"]):
        lp = make(key, i)
        for qn in quants:
            out = [step(x, lp, *w, qn) for x, w in zip(streams[qn], where)]
            streams[qn] = [x for x, _ in out]
            if qn is None:
                tightest = [jnp.where(b < a, i, t) for t, a, (_, b) in zip(tightest, margins, out)]
                margins = [jnp.minimum(a, b) for a, (_, b) in zip(margins, out)]
        del lp, out
    jax.block_until_ready(list(streams.values()))
    t_layers = time.monotonic() - t0

    logits_at = jax.jit(lambda top, x, at, qn: head(top, x[at], m, qn), static_argnums=3)
    tokens = []
    for j, ((_, served), pl) in enumerate(zip(samples, plans)):
        k = served.size
        at = jnp.asarray(np.concatenate([pl["rows"], np.zeros((n_rows - k,), np.int64)]))
        logits = logits_at(top, streams[None][j], at, None)[:k]
        best = jnp.max(logits, axis=-1)
        gap = best - jnp.take_along_axis(logits, jnp.asarray(served)[:, None], axis=-1)[:, 0]
        low_gap = gap
        if quant is not None:
            low = jnp.argmax(logits_at(top, streams[quant][j], at, quant)[:k], axis=-1)
            low_gap = best - jnp.take_along_axis(logits, low[:, None], axis=-1)[:, 0]
        tokens.append({"gap": np.asarray(gap), "low_gap": np.asarray(low_gap),
                       "margin": np.asarray(margins[j][at][:k]),
                       "layer": np.asarray(tightest[j][at][:k])})
    log(f"reference: {len(plans)} sequences padded to {length} + {m['denoise_steps']} x {copy}, "
        f"{n_rows} served positions each; layers {t_layers:.1f}s, "
        f"head {time.monotonic() - t0 - t_layers:.1f}s")
    return tokens


def serve_gaps(m: dict, seed: int, samples, *, quant=None) -> dict:
    """`judge_tokens` of the served tokens of `samples` (`token_gaps`): the
    widest gap by which a served token's logit lies below the reference's
    best, in the pass that unmasked it, among the tokens that choose their
    experts robustly, or the quantile of the others' if that is larger; with
    `quant`, beside it the same of the token the lower precision puts first
    (the control). Prints what a later choice of the margin needs."""
    tokens = token_gaps(m, seed, samples, quant=quant)
    gap, low, margin = (
        np.concatenate([t[k] for t in tokens]) if tokens else np.zeros(0)
        for k in ("gap", "low_gap", "margin")
    )
    if gap.size:
        widest = lambda a: float(a.max()) if a.size else 0.0  # noqa: E731
        cuts = (0.0, 0.002, 0.005, 0.0075, 0.01, 0.02)
        log(f"reference: {gap.size} served tokens; by router margin from "
            + " / ".join(str(c) for c in cuts) + ": share of tokens "
            + " / ".join(f"{float(np.mean(margin >= c)):.3f}" for c in cuts) + ", widest gap "
            + " / ".join(f"{widest(gap[margin >= c]):.4f}" for c in cuts) + ", of the control's choice "
            + " / ".join(f"{widest(low[margin >= c]):.4f}" for c in cuts)
            + f"; share of gaps over 0 / 0.05 / 0.1: {float(np.mean(gap > 0)):.4f} / "
            f"{float(np.mean(gap > 0.05)):.4f} / {float(np.mean(gap > 0.1)):.4f}, of the control's "
            f"{float(np.mean(low > 0)):.4f} / {float(np.mean(low > 0.05)):.4f} / {float(np.mean(low > 0.1)):.4f}")
    return {"widest_gap": judge_tokens(gap, margin), "widest_gap_low": judge_tokens(low, margin),
            "tokens": int(gap.size)}
