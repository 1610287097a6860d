"""The GPT-2 family: the only file of the benchmark that knows this
architecture. A configuration's file names it (`"family": "gpt2"`), and
`manifest.load_family` finds it by that name.

It holds the program's module built from a configuration's `model` group
(vocab_size, n_ctx, n_embd, n_layer, n_head), the weights from `--seed`,
the plain reference (GPT-2 in straightforward `jax.numpy`, float32 at
`highest` matmul precision, with its loss and gradients; no kernels, no
cache, no batching tricks; it takes nothing the program has made), and the
operations and bytes worked out from shapes.

Weights: made on the device in one jitted call, in float32 and in the
layout the program's GPT-2 declares under `scan_layers` (per-layer leaves
stacked on a leading layer axis); the same values go to the program and to
the reference. Memory: the training reference runs row block by row block
and layer by layer, adding each layer's gradient into one accumulator in
place (`reference.follow_steps` drives it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness import reference as R

# The scopes inside a block and after it: what `decode_carry_share.serve`
# leaves out, and the labels of the tables. Flax names the Dense and
# LayerNorm modules; the program names the rest (PERF.md section 3).
BLOCK_SCOPES = (
    "attn_core", "kv_read", "kv_write", "c_attn", "c_proj", "mlp_fc", "mlp_proj",
    "ln_1", "ln_2", "ln_f", "lm_head", "sample",
)
MODULE_SCOPES = ("block", "GPT2")  # flax's own, around everything in the model


# ------------------------------------------------------------ the program
def module(m: dict):
    """The program's flax module for a configuration's `model` group."""
    from tpuflow.models.gpt2 import GPT2, GPT2Config

    fields = dict(m)
    if "dtype" in fields:
        fields["dtype"] = jnp.dtype(fields["dtype"])
    return GPT2(GPT2Config(**fields))


def positions(m: dict) -> int:
    """The longest sequence a request may reach."""
    return m["n_ctx"]


def vocabulary(m: dict) -> int:
    """The ids the traffic may draw."""
    return m["vocab_size"]


def check_config(cfg: dict) -> list[str]:
    """Problems with a configuration's file (empty = none): widths, depth
    and positions as published, and the parameter count the file states."""
    bad = []
    for key in ("n_embd", "n_layer", "n_head", "vocab_size"):
        if cfg["model"][key] != cfg[key]:
            bad.append(f"model.{key} {cfg['model'][key]} is not the published {cfg[key]}")
    if cfg["model"]["n_ctx"] != cfg["n_positions"]:
        bad.append(f"model.n_ctx {cfg['model']['n_ctx']} is not n_positions {cfg['n_positions']}")
    if n_params(cfg["model"]) != cfg["parameters"]:
        bad.append(f"parameters {cfg['parameters']} is not the {n_params(cfg['model'])} of the shapes")
    return bad


def test_config() -> dict:
    """A configuration at the `test` width for the CPU rehearsals, with the
    limits of `correct` at that size. Set as the cells' own are, from
    readings at this size on the CPU: the program's bf16 path reads loss
    gaps of 3e-6 and a gradient gap of 0.002; the float8 control reads 8e-5
    to 2e-4 on the first loss; half a batch reads 0.4 to 0.5 on the
    gradient (the control 0.024 to 0.033); a state left unchanged reads 1.
    The third loss is not compared, as in the cells' own files: at the
    cells' size it carries the rounding of two Adam updates (PERF.md
    section 6)."""
    return {
        "model": {"vocab_size": 512, "n_ctx": 128, "n_embd": 64, "n_layer": 2, "n_head": 4,
                  "dropout": 0.0, "ln_eps": 1e-05, "attn_impl": "auto", "dtype": "bfloat16",
                  "remat": True, "scan_layers": True},
        "optimizer": {"learning_rate": 3e-4, "optimizer": "adamw", "weight_decay": 1e-4,
                      "schedule": "constant"},
        "serve": {"max_slots": 4, "paged": True, "prefix_cache": True, "speculative": 0,
                  "quant": None, "decode_block": 4},
        "limits": {
            "train": {"loss1_gap": 3e-5, "loss2_gap": 3e-5, "grad_gap": 0.01, "dparam_gap": 0.3},
            "serve": {"widest_logit_gap": 0.05},
        },
    }


# ------------------------------- operations and bytes worked out from shapes
def n_params(m: dict) -> int:
    """Parameters of a GPT-2 with a tied head: embeddings, per layer two
    LayerNorms, attention (qkv + proj) and MLP (fc + proj), final LayerNorm."""
    c, v, t, l = m["n_embd"], m["vocab_size"], m["n_ctx"], m["n_layer"]
    per_layer = (
        2 * 2 * c                # ln_1, ln_2: scale + bias
        + c * 3 * c + 3 * c      # c_attn
        + c * c + c              # c_proj
        + c * 4 * c + 4 * c      # mlp_fc
        + 4 * c * c + c          # mlp_proj
    )
    return v * c + t * c + l * per_layer + 2 * c


def train_flops_per_token(m: dict) -> float:
    """6·N: forward 2·N and backward 4·N per token; remat's recomputation
    and the attention-score products are not counted (so the share reads low,
    never high)."""
    return 6.0 * n_params(m)


def forward_flops_per_token(m: dict) -> float:
    """2·N per token computed (prefill or decode)."""
    return 2.0 * n_params(m)


def decode_step_bytes(m: dict, live_context_tokens: int, weight_bytes: int = 4,
                      cache_bytes: int = 4) -> float:
    """Bytes one decode step has to read: every weight once (the position
    table aside: one row per slot) and the keys and values of the live
    context once. `live_context_tokens` is the sum over live slots of their
    current lengths."""
    c, l = m["n_embd"], m["n_layer"]
    weights = (n_params(m) - m["n_ctx"] * c) * weight_bytes
    kv = 2 * l * live_context_tokens * c * cache_bytes
    return float(weights + kv)


def attention_flops(m: dict, batch: int, seq: int, steps: float) -> float:
    """The least causal attention has to do in `steps` training steps: per
    layer the score and the value products, 4·B·H·T²·D forward, half of it
    under the causal mask, three times that with the backward pass. What
    remat computes again is not counted."""
    head_dim = m["n_embd"] // m["n_head"]
    per_layer = 3 * 0.5 * 4.0 * batch * m["n_head"] * seq * seq * head_dim
    return steps * m["n_layer"] * per_layer


# -------------------------------------------------- weights from `--seed`
def leaf_table(m: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], float, float]]:
    """(path, shape, mean, std) of every leaf, in a fixed order."""
    c, v, t, l = m["n_embd"], m["vocab_size"], m["n_ctx"], m["n_layer"]
    proj_std = 0.02 / (2.0 * l) ** 0.5  # GPT-2's scaled residual projections
    blk = ("h", "block")
    return [
        (("wte",), (v, c), 0.0, 0.02),
        (("wpe",), (t, c), 0.0, 0.01),
        (blk + ("ln_1", "scale"), (l, c), 1.0, 0.02),
        (blk + ("ln_1", "bias"), (l, c), 0.0, 0.01),
        (blk + ("c_attn", "kernel"), (l, c, 3 * c), 0.0, 0.02),
        (blk + ("c_attn", "bias"), (l, 3 * c), 0.0, 0.01),
        (blk + ("c_proj", "kernel"), (l, c, c), 0.0, proj_std),
        (blk + ("c_proj", "bias"), (l, c), 0.0, 0.01),
        (blk + ("ln_2", "scale"), (l, c), 1.0, 0.02),
        (blk + ("ln_2", "bias"), (l, c), 0.0, 0.01),
        (blk + ("mlp_fc", "kernel"), (l, c, 4 * c), 0.0, 0.02),
        (blk + ("mlp_fc", "bias"), (l, 4 * c), 0.0, 0.01),
        (blk + ("mlp_proj", "kernel"), (l, 4 * c, c), 0.0, proj_std),
        (blk + ("mlp_proj", "bias"), (l, c), 0.0, 0.01),
        (("ln_f", "scale"), (c,), 1.0, 0.02),
        (("ln_f", "bias"), (c,), 0.0, 0.01),
    ]


def make_leaf(m: dict, key, index: int):
    """Leaf `index` of `leaf_table`, float32."""
    _, shape, mean, std = leaf_table(m)[index]
    k = jax.random.fold_in(key, index)
    return mean + std * jax.random.normal(k, shape, jnp.float32)


def make_params(m: dict, key) -> dict:
    """The whole parameter tree (call under `jit`)."""
    tree: dict = {}
    for i, (path, _, _, _) in enumerate(leaf_table(m)):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = make_leaf(m, key, i)
    return tree


def leaf_name(path) -> str:
    return "/".join(path)


def get_leaf(tree, path):
    for part in path:
        tree = tree[part]
    return tree


QKV_PARTS = ("q", "k", "v")


def leaf_norms(tree, m: dict, *, minus_key=None, scale: float = 1.0) -> dict[str, float]:
    """The norm of every leaf (times `scale`), one leaf at a time; with
    `minus_key`, of the leaf less the initial leaf that key makes. The fused
    query-key-value leaves are read as three, so that the key's bias, whose
    gradient is nought under softmax, stands alone."""

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def one(leaf, index, parts, key):
        if key is not None:
            leaf = leaf - make_leaf(m, key, index)
        return [jnp.linalg.norm(p.ravel()) for p in jnp.split(leaf, parts, axis=-1)]

    out = {}
    for i, (path, _, _, _) in enumerate(leaf_table(m)):
        fused = "c_attn" in path
        norms = one(get_leaf(tree, path), i, 3 if fused else 1, minus_key)
        for part, n in zip(QKV_PARTS if fused else ("",), norms):
            name = leaf_name(path) + (f"[{part}]" if part else "")
            out[name] = float(n) * scale
    return out


# ---------------------------------------------------- the plain reference
def _ln(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def layer(x, lp, n_head: int, eps: float, q):
    """One pre-LN block on (R, T, C)."""
    r, t, c = x.shape
    d = c // n_head
    h = _ln(x, lp["ln_1"], eps)
    qkv = jnp.matmul(q(h), q(lp["c_attn"]["kernel"]), precision=R.HIGHEST)
    qkv = qkv + lp["c_attn"]["bias"]
    qh, kh, vh = (a.reshape(r, t, n_head, d) for a in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(kh), precision=R.HIGHEST)
    s = s / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", q(p), q(vh), precision=R.HIGHEST)
    a = a.reshape(r, t, c)
    x = x + jnp.matmul(q(a), q(lp["c_proj"]["kernel"]), precision=R.HIGHEST) + lp["c_proj"]["bias"]
    h = _ln(x, lp["ln_2"], eps)
    h = jnp.matmul(q(h), q(lp["mlp_fc"]["kernel"]), precision=R.HIGHEST) + lp["mlp_fc"]["bias"]
    h = _gelu(h)
    h = jnp.matmul(q(h), q(lp["mlp_proj"]["kernel"]), precision=R.HIGHEST) + lp["mlp_proj"]["bias"]
    return x + h


def _embed(params, tokens):
    t = tokens.shape[1]
    return params["wte"][tokens] + params["wpe"][:t][None]


def _head_logits(x, ln_f, wte, eps, q):
    x = _ln(x, ln_f, eps)
    return jnp.matmul(q(x), q(wte).T, precision=R.HIGHEST)


def forward_logits(params, tokens, m: dict, quant=None):
    """(R, T) token ids -> (R, T, V) logits."""
    q = R.QUANT[quant]
    eps = m.get("ln_eps", 1e-5)

    def body(x, lp):
        return layer(x, lp, m["n_head"], eps, q), None

    x, _ = lax.scan(body, _embed(params, tokens), params["h"]["block"])
    return _head_logits(x, params["ln_f"], params["wte"], eps, q)


def _take_layer(tree, l):
    return jax.tree_util.tree_map(
        lambda a: lax.dynamic_index_in_dim(a, l, 0, keepdims=False), tree
    )


def _block_grad(params, g_acc, x_tok, y_tok, scale, *, m, quant):
    """Loss (times `scale`) of one block of rows, and its gradient added
    into `g_acc` layer by layer."""
    q = R.QUANT[quant]
    eps = m.get("ln_eps", 1e-5)
    n_head, n_layer = m["n_head"], m["n_layer"]
    layers = params["h"]["block"]
    fn = functools.partial(layer, n_head=n_head, eps=eps, q=q)

    def fwd(x, lp):
        return fn(x, lp), x

    x_last, xs = lax.scan(fwd, _embed(params, x_tok), layers)

    def head(x, ln_f, wte):
        logits = _head_logits(x, ln_f, wte, eps, q)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, y_tok[..., None], axis=-1)[..., 0]
        return jnp.sum(nll) * scale

    loss, (dx, d_lnf, d_wte) = jax.value_and_grad(head, argnums=(0, 1, 2))(
        x_last, params["ln_f"], params["wte"]
    )

    def bwd(i, carry):
        dx, g = carry
        l = n_layer - 1 - i
        x_in = lax.dynamic_index_in_dim(xs, l, 0, keepdims=False)
        _, vjp = jax.vjp(fn, x_in, _take_layer(layers, l))
        dx_in, d_lp = vjp(dx)
        g = jax.tree_util.tree_map(
            lambda acc, d: lax.dynamic_update_index_in_dim(
                acc, lax.dynamic_index_in_dim(acc, l, 0, keepdims=False) + d, l, 0
            ),
            g, d_lp,
        )
        return dx_in, g

    dx0, g_layers = lax.fori_loop(0, n_layer, bwd, (dx, g_acc["h"]["block"]))
    t = x_tok.shape[1]
    g_wte = (g_acc["wte"] + d_wte).at[x_tok.reshape(-1)].add(
        dx0.reshape(-1, dx0.shape[-1])
    )
    g_wpe = g_acc["wpe"].at[:t].add(jnp.sum(dx0, axis=0))
    g_lnf = jax.tree_util.tree_map(jnp.add, g_acc["ln_f"], d_lnf)
    return loss, {
        "wte": g_wte, "wpe": g_wpe, "h": {"block": g_layers}, "ln_f": g_lnf,
    }


def delta_norms(params, m: dict, seed: int) -> dict[str, float]:
    """Per leaf, the norm of (params - the seed's initial leaf)."""
    return leaf_norms(params, m, minus_key=R.seed_key(seed))


def train_reference(m: dict, opt: dict, seed: int, batches, *, quant=None,
                    rows_per_block: int = 1, fault: str | None = None) -> dict:
    """Follow `len(batches)` AdamW steps from the seed's weights: each
    step's loss, the per-leaf norm of the first gradient and the per-leaf
    norm of the parameters' change after the last step (`quant`, `fault`:
    `reference.follow_steps`)."""
    params = jax.jit(lambda k: make_params(m, k))(R.seed_key(seed))
    block = jax.jit(
        functools.partial(_block_grad, m=m, quant=quant), donate_argnums=(1,)
    )
    out, params = R.follow_steps(
        params, opt, batches, block, lambda g: leaf_norms(g, m),
        rows_per_block=rows_per_block, fault=fault,
    )
    out["dparam_norms"] = delta_norms(params, m, seed)
    return out


def serve_gaps(m: dict, seed: int, samples, *, quant=None) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over `samples` of (prompt ids, served ids)
    (`reference.teacher_forced_gaps`)."""
    params = jax.jit(lambda k: make_params(m, k))(R.seed_key(seed))
    return R.teacher_forced_gaps(
        params, lambda p, tokens, qn: forward_logits(p, tokens, m, qn), samples,
        positions(m), quant=quant,
    )
