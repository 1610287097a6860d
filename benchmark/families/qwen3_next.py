"""The Qwen3-Next family (Qwen, `model_type: qwen3_next`): the only file of
the benchmark that knows this architecture. A configuration's file names it
(`"family": "qwen3_next"`), and `manifest.load_family` finds it by that name.

It holds the program's module built from a configuration's `model` group,
the weights from `--seed`, the plain reference, and the operations and bytes
worked out from shapes. The family trains in no cell: no training functions.

The plain reference is the forward pass of the equations in
`tpuflow/models/qwen3_next.py`'s docstring in straightforward `jax.numpy`,
float32 at `highest` matmul precision, teacher forced over one sequence: the
Gated DeltaNet's state S moved on token by token exactly as written (a scan
over the sequence: no chunks, no cache, no slot rows), causal softmax
attention with full heads, the routed experts as a dense sum over the
experts held here, each weighted by what the router gave the token (nought
where it was not chosen or lives on another chip). It takes nothing the
program has made but the weights' recipe. Weights: bfloat16, every layer
keyed by its index and every expert by its id among all the router's, so
that the program makes the whole tree in one jitted call (`make_params`) and
the reference makes one layer at a time inside its layer loop and drops it
(`token_gaps`); a share of the experts is the same experts whichever share
is held.
"""

from __future__ import annotations

import functools
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
# leaves out, and the labels of the tables (PERF.md section 3). `sample` is
# the engine's.
BLOCK_SCOPES = (
    "gdn_in", "gdn_conv", "gdn_core", "gdn_out", "attn_q", "attn_gate",
    "kv_write", "kv_read", "attn_core", "attn_out", "router", "moe_experts",
    "moe_shared", "lm_head", "sample",
)
MODULE_SCOPES = ("layer", "gdn", "attn", "periods", "Qwen3Next")  # flax's own

# The published widths (the configuration's top-level keys) and the key of
# the `model` group each has to equal.
PUBLISHED = {
    "hidden_size": "hidden_size", "num_attention_heads": "n_head",
    "num_key_value_heads": "n_kv_head", "head_dim": "head_dim",
    "full_attention_interval": "full_attention_interval",
    "partial_rotary_factor": "partial_rotary_factor", "rope_theta": "rope_theta",
    "linear_num_key_heads": "linear_num_key_heads",
    "linear_num_value_heads": "linear_num_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel_dim",
    "moe_intermediate_size": "moe_intermediate_size",
    "shared_expert_intermediate_size": "shared_expert_intermediate_size",
    "num_experts_per_tok": "n_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob", "rms_norm_eps": "rms_norm_eps",
}
# Cut here, each key at the top level the number served (the `published`
# group keeps the source's): depth, the experts and the vocabulary held (the
# chip's share), positions.
SERVED = {"num_hidden_layers": "n_layer", "num_experts": "experts_held",
          "vocab_size": "vocab_size", "max_position_embeddings": "n_ctx"}


# ------------------------------------------------------------ the program
def module(m: dict):
    """The program's flax module for a configuration's `model` group. A
    program from before the model was written cannot run the family's
    cells: it is told so at once, before anything is built."""
    try:
        from tpuflow.models.qwen3_next import Qwen3Next, Qwen3NextConfig
    except ImportError as e:
        raise manifest.ManifestError(
            f"family 'qwen3_next' needs tpuflow/models/qwen3_next.py, which this program lacks ({e})"
        ) from e

    fields = dict(m)
    fields["dtype"] = jnp.dtype(fields.get("dtype", "bfloat16"))
    return Qwen3Next(Qwen3NextConfig(**fields))


def positions(m: dict) -> int:
    """The longest sequence a request may reach."""
    return m["n_ctx"]


def vocabulary(m: dict) -> int:
    """The ids the traffic may draw: the slice of the vocabulary held here."""
    return m["vocab_size"]


def _kinds(m: dict) -> list[str]:
    """Layer i's kind: `gdn`, or `attn` every `full_attention_interval`-th."""
    n = m["full_attention_interval"]
    return ["attn" if (i + 1) % n == 0 else "gdn" for i in range(m["n_layer"])]


def check_config(cfg: dict) -> list[str]:
    """Problems with a configuration's file (empty = none): every width as
    published, each cut the file lists and reduces to, a whole number of
    periods, no dense layer, and both parameter counts it states (the
    published model's and what this chip holds)."""
    bad = []
    m = cfg["model"]
    for key, mine in PUBLISHED.items():
        if m[mine] != cfg[key]:
            bad.append(f"model.{mine} {m[mine]} is not the published {key} {cfg[key]}")
    if cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        bad.append("a dense layer in the pattern: the model has none (intermediate_size unused)")
    published = cfg["published"]
    for key, mine in SERVED.items():
        if m[mine] != cfg[key]:
            bad.append(f"model.{mine} {m[mine]} is not the {key} {cfg[key]} served")
        if key not in cfg["reduced"] and published[key] != cfg[key]:
            bad.append(f"{key} differs from the source and is not in reduced")
    if m["n_experts"] != published["num_experts"]:
        bad.append(f"the router's {m['n_experts']} outputs are not the published "
                   f"num_experts {published['num_experts']}")
    if m["n_layer"] % m["full_attention_interval"]:
        bad.append(f"{m['n_layer']} layers are not whole periods of {m['full_attention_interval']}")
    if not 0 <= m.get("experts_first", 0) <= m["n_experts"] - m["experts_held"]:
        bad.append("the experts held are not a range of the router's")
    if n_params(m) != cfg["parameters"]:
        bad.append(f"parameters {cfg['parameters']} is not the {n_params(m)} of the shapes")
    whole = dict(m, n_layer=published["num_hidden_layers"],
                 experts_held=published["num_experts"], vocab_size=published["vocab_size"])
    if n_params(whole) != cfg["parameters_published"]:
        bad.append(f"parameters_published {cfg['parameters_published']} is not the "
                   f"{n_params(whole)} of the published shapes")
    return bad


def test_config() -> dict:
    """A configuration at the `test` width for the CPU tests, with the
    limit of `correct` at that size (float32 program against the float32
    reference: the order of the sums alone, 1e-5 of a logit; the float8
    control reads 1e-2 and more there). Two periods, as the cell, and a
    quarter of the router's experts held, as the cell; chunks of 8 so that
    a prompt spans several."""
    return {
        "model": {
            "vocab_size": 256, "n_ctx": 128, "hidden_size": 64, "n_layer": 8,
            "full_attention_interval": 4, "n_head": 4, "n_kv_head": 2, "head_dim": 32,
            "partial_rotary_factor": 0.25, "rope_theta": 10000000.0,
            "linear_num_key_heads": 2, "linear_num_value_heads": 4,
            "linear_key_head_dim": 16, "linear_value_head_dim": 8,
            "linear_conv_kernel_dim": 4, "moe_intermediate_size": 16,
            "shared_expert_intermediate_size": 16, "n_experts": 16,
            "n_experts_per_tok": 3, "experts_held": 4, "experts_first": 0,
            "norm_topk_prob": True, "rms_norm_eps": 1e-6, "gdn_chunk": 8,
            "dtype": "float32",
        },
        "serve": {"max_slots": 4, "paged": True, "prefix_cache": True, "speculative": 0,
                  "quant": None, "decode_block": 4},
        "limits": {"serve": {"widest_logit_gap": 1e-3}},
    }


# ------------------------------- operations and bytes worked out from shapes
ROUTED = ("experts_gate_up", "experts_down")


def _layer_shapes(m: dict, kind: str) -> dict[str, tuple[int, ...]]:
    """Every leaf of one layer, by the name the program's module gives it,
    the experts held here among them."""
    c, e, f = m["hidden_size"], m["n_experts"], m["moe_intermediate_size"]
    fs = m["shared_expert_intermediate_size"]
    out = {
        "attn_norm": (c,), "mlp_norm": (c,), "router": (c, e),
        "shared_gate_up": (c, 2 * fs), "shared_down": (fs, c), "shared_gate": (c, 1),
        "experts_gate_up": (m["experts_held"], c, 2 * f),
        "experts_down": (m["experts_held"], f, c),
    }
    if kind == "attn":
        h, g, d = m["n_head"], m["n_kv_head"], m["head_dim"]
        out.update({"q": (c, h * 2 * d), "k": (c, g * d), "v": (c, g * d),
                    "q_norm": (d,), "k_norm": (d,), "o": (h * d, c)})
    else:
        hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
        dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
        conv = 2 * hk * dk + hv * dv
        out.update({"in_qkvz": (c, conv + hv * dv), "in_ba": (c, 2 * hv),
                    "conv": (m["linear_conv_kernel_dim"], conv), "A_log": (hv,),
                    "dt_bias": (hv,), "out_norm": (dv,), "out": (hv * dv, c)})
    return out


def _count(shapes: dict, skip=()) -> int:
    return sum(math.prod(s) for k, s in shapes.items() if k not in skip)


def n_params(m: dict) -> int:
    """Parameters held: embedding, untied head, final norm, every layer with
    the experts held."""
    c, v = m["hidden_size"], m["vocab_size"]
    return 2 * v * c + c + sum(_count(_layer_shapes(m, k)) for k in _kinds(m))


def _routed_expert_params(m: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _resident_params(m: dict) -> int:
    """What every forward pass reads whatever is routed: everything but
    the embedding table (a row a token) and the routed experts."""
    c, v = m["hidden_size"], m["vocab_size"]
    return v * c + c + sum(_count(_layer_shapes(m, k), skip=ROUTED) for k in _kinds(m))


def routed_experts_held(m: dict) -> int:
    """Routed experts over all layers: the most a step can touch here."""
    return m["n_layer"] * m["experts_held"]


def _routed_here(m: dict) -> float:
    """A token's routed experts that lie here, on average: its
    `n_experts_per_tok` spread over the router's `n_experts`."""
    return m["n_experts_per_tok"] * m["experts_held"] / m["n_experts"]


def forward_flops_per_token(m: dict) -> float:
    """2 x the parameters a token uses here: everything resident and the
    share of its routed experts that this chip holds (10 x 128 / 512 = 2.5
    a layer). The attention-score products and the delta rule's own
    arithmetic are not counted (the share reads low)."""
    return 2.0 * (_resident_params(m) + m["n_layer"] * _routed_here(m) * _routed_expert_params(m))


def train_flops_per_token(m: dict) -> float:
    """Forward and backward, three times the forward (no cell trains this
    family: `mfu.train` would ask)."""
    return 3.0 * forward_flops_per_token(m)


def attention_flops(m: dict, batch: int, seq: int, steps: float) -> float:
    """The least causal attention has to do in `steps` training steps, in
    the gated attention layers (scores and values over `head_dim`, half under
    the mask, three times with the backward pass)."""
    per_layer = 3 * 0.5 * 2.0 * batch * m["n_head"] * seq * seq * 2 * m["head_dim"]
    return steps * _kinds(m).count("attn") * per_layer


def moe_step_bytes(m: dict, experts_touched: float, weight_bytes: int = 2) -> float:
    """Bytes the routed-expert products have to read for `experts_touched`
    (distinct experts, summed over layers and steps): each one's three
    matrices once."""
    return float(experts_touched * _routed_expert_params(m) * weight_bytes)


def state_step_bytes(m: dict, row_steps: float, state_bytes: int = 4) -> float:
    """Bytes the Gated DeltaNet layers' state has to move for `row_steps`
    (live rows summed over decode steps: the `state_rows` the model sows):
    every such layer's S and conv state read once and written once."""
    hv = m["linear_num_value_heads"]
    s = hv * m["linear_key_head_dim"] * m["linear_value_head_dim"]
    conv = (m["linear_conv_kernel_dim"] - 1) * (
        2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
        + hv * m["linear_value_head_dim"])
    return float(2 * row_steps * _kinds(m).count("gdn") * (s + conv) * state_bytes)


def decode_step_bytes(m: dict, live_context_tokens: int, weight_bytes: int = 2,
                      cache_bytes: int = 2) -> float:
    """The least one decode step has to read and write: everything resident
    once, the routed experts one live row's token touches here (its share of
    `n_experts_per_tok` a layer: the reader is not told how many rows are
    live, and every further row only adds), one row's state, and the keys
    and values of the live context once in the attention layers."""
    kv = 2 * m["n_kv_head"] * m["head_dim"]
    return float(
        _resident_params(m) * weight_bytes
        + moe_step_bytes(m, m["n_layer"] * _routed_here(m), weight_bytes)
        + state_step_bytes(m, 1)
        + _kinds(m).count("attn") * live_context_tokens * kv * cache_bytes
    )


# -------------------------------------------------- weights from `--seed`
PUBLISHED_HIDDEN = 2048
TOP_KEY = 1_000_000  # a fold-in beside the layers' indices
OUT_PROJECTIONS = ("o", "out", "experts_down", "shared_down")


def _leaf(key, name: str, shape, m: dict, first: int = 0):
    """One leaf, bfloat16-rounded values in the model's dtype. Sizes: 0.02
    for matrices (the out-projections 0.02 / sqrt(2 L)); the embedding 1
    (Xing4's reasoning: a stream that is only its last sub-layers' outputs
    is one that rounding throws about); zero-centred norms 0.02 around
    nought, the gated norm's scale around 1; the convolution 0.5 (its four
    taps then keep the variance of its input); `A_log` the log of a uniform
    draw over [1, 16] and `dt_bias` the inverse softplus of a log-uniform
    draw over [0.001, 0.1] (Mamba2's ranges, which Qwen3-Next's Gated
    DeltaNet follows: per-token decays from about 0.98 to nearly 1, so that
    the state carries a long context). Routed experts are drawn by their
    id among the router's (`first` the first held), so a chip's share holds
    the same experts as the whole layer does."""
    wide = math.sqrt(PUBLISHED_HIDDEN / m["hidden_size"])
    dtype = jnp.dtype(m.get("dtype", "bfloat16"))
    if name in ROUTED:
        scale = (0.02 / math.sqrt(2 * m["n_layer"]) if name == "experts_down" else 0.02) * wide
        ids = first + jnp.arange(shape[0])
        z = jax.vmap(lambda e: jax.random.normal(jax.random.fold_in(key, e), shape[1:]))(ids)
        return (scale * z).astype(jnp.bfloat16).astype(dtype)
    if name == "A_log":
        val = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
        return val.astype(jnp.float32)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "out_norm":
        val = 1.0 + 0.02 * z
    elif "norm" in name:
        val = 0.02 * z
    elif name == "conv":
        val = 0.5 * z
    elif name in OUT_PROJECTIONS:
        val = 0.02 * wide / math.sqrt(2 * m["n_layer"]) * z
    elif name == "embed":
        val = z
    else:
        val = 0.02 * wide * z
    return val.astype(jnp.bfloat16).astype(dtype)


def _leaf_ids(m: dict) -> dict[str, int]:
    """A number for every leaf name a layer of either kind has: its key's
    fold-in, the same whatever the layer's kind."""
    names = set(_layer_shapes(m, "gdn")) | set(_layer_shapes(m, "attn"))
    return {name: j for j, name in enumerate(sorted(names))}


def layer_params(m: dict, key, index, kind: str, names=None) -> dict:
    """Layer `index`'s leaves (call under `jit`; `index` may be traced), all
    of them or `names` alone."""
    k = jax.random.fold_in(key, index)
    first, ids = m.get("experts_first", 0), _leaf_ids(m)
    return {
        name: _leaf(jax.random.fold_in(k, ids[name]), name, shape, m, first)
        for name, shape in sorted(_layer_shapes(m, kind).items())
        if names is None or name in names
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
    declares it: the experts of every layer in two leaves of their own, in
    the layers' order; the rest of each period's layers stacked, the Gated
    DeltaNet ones (periods, 3, ...)."""
    tree = top_params(m, key)
    kinds = _kinds(m)
    n = m["full_attention_interval"]
    periods = m["n_layer"] // n
    experts = lax.map(
        lambda i: layer_params(m, key, i, "gdn", ROUTED), jnp.arange(m["n_layer"])
    )
    tree.update(experts)
    rest = lambda kind: {k for k in _layer_shapes(m, kind) if k not in ROUTED}  # noqa: E731
    gdn = jnp.asarray([i for i, k in enumerate(kinds) if k == "gdn"])
    attn = jnp.asarray([i for i, k in enumerate(kinds) if k == "attn"])
    gdn_stack = lax.map(lambda i: layer_params(m, key, i, "gdn", rest("gdn")), gdn)
    gdn_stack = jax.tree_util.tree_map(lambda a: a.reshape((periods, n - 1) + a.shape[1:]), gdn_stack)
    attn_stack = lax.map(lambda i: layer_params(m, key, i, "attn", rest("attn")), attn)
    tree["periods"] = {"gdn": {"layer": gdn_stack}, "attn": attn_stack}
    return tree


def layer_of(params, i: int, m: dict) -> dict:
    """Layer `i`'s leaves out of a whole tree, its experts included."""
    n = m["full_attention_interval"]
    p, j = divmod(i, n)
    if j == n - 1:
        lp = jax.tree_util.tree_map(lambda a: a[p], params["periods"]["attn"])
    else:
        lp = jax.tree_util.tree_map(lambda a: a[p, j], params["periods"]["gdn"]["layer"])
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
    """No cell trains this family (training through the delta rule's scan
    is left for a later PR: ROADMAP.md, M5), so no reference of a training
    step was written: say so where one is asked for."""
    raise NotImplementedError(
        "the qwen3_next family trains in no cell: its reference is the forward pass alone"
    )


# ---------------------------------------------------- the plain reference
def _mm(a, b, q):
    return jnp.matmul(q(a), q(b), precision=R.HIGHEST)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, m: dict):
    """Rotate the halves of the first `partial_rotary_factor` of each head
    of (T, H, D) by the position's angles; the rest as it is."""
    t, d = x.shape[0], int(m["head_dim"] * m["partial_rotary_factor"])
    inv = 1.0 / m["rope_theta"] ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * inv
    x1, x2, rest = x[..., : d // 2], x[..., d // 2:d], x[..., d:]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang), x2 * jnp.cos(ang) + x1 * jnp.sin(ang), rest],
        axis=-1,
    )


def _attention(x, lp, m: dict, q):
    t = x.shape[0]
    h, g, d, eps = m["n_head"], m["n_kv_head"], m["head_dim"], m["rms_norm_eps"]
    qg = _mm(x, lp["q"], q).reshape(t, h, 2 * d)
    gate = qg[..., d:]
    qh = _rope(_rms(qg[..., :d], 1.0 + lp["q_norm"], eps), m)
    kh = _rope(_rms(_mm(x, lp["k"], q).reshape(t, g, d), 1.0 + lp["k_norm"], eps), m)
    vh = _mm(x, lp["v"], q).reshape(t, g, d)
    kh, vh = (jnp.repeat(a, h // g, axis=1) for a in (kh, vh))
    s = jnp.einsum("qhd,khd->hqk", q(qh), q(kh), precision=R.HIGHEST) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    a = jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(s, axis=-1)), q(vh), precision=R.HIGHEST)
    return _mm((a * jax.nn.sigmoid(gate)).reshape(t, h * d), lp["o"], q)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _state_round(quant):
    """What the state S goes through after every token: nothing, or (the
    `state_bf16` control) a rounding to bfloat16."""
    return R._bf16 if quant == "state_bf16" else (lambda s: s)


def _gated_delta(x, lp, m: dict, q, quant=None):
    """The Gated DeltaNet mixer on (T, C), token by token."""
    t = x.shape[0]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv, width = m["linear_key_head_dim"], m["linear_value_head_dim"], m["linear_conv_kernel_dim"]
    n_conv = 2 * hk * dk + hv * dv
    qkvz = _mm(x, lp["in_qkvz"], q)
    ba = _mm(x, lp["in_ba"], q)
    mixed, z = qkvz[:, :n_conv], qkvz[:, n_conv:].reshape(t, hv, dv)
    padded = jnp.concatenate([jnp.zeros((width - 1, n_conv)), mixed])
    mixed = jax.nn.silu(sum(lp["conv"][j] * padded[j:j + t] for j in range(width)))
    qh = _l2(mixed[:, :hk * dk].reshape(t, hk, dk)) * dk ** -0.5
    kh = _l2(mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    vh = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)
    qh, kh = (jnp.repeat(a, hv // hk, axis=1) for a in (qh, kh))
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, hv:] + lp["dt_bias"])
    keep = _state_round(quant)

    def one(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[:, None, None]
        delta = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt, precision=R.HIGHEST))
        s = keep(s + kt[:, :, None] * delta[:, None, :])
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=R.HIGHEST)

    _, o = lax.scan(one, jnp.zeros((hv, dk, dv)), (qh, kh, vh, g, beta))
    o = _rms(o, lp["out_norm"], m["rms_norm_eps"]) * jax.nn.silu(z)
    return _mm(o.reshape(t, hv * dv), lp["out"], q)


def router_logits(x, lp, q=R.QUANT[None]):
    return _mm(x, lp["router"], q)


def router(x, lp, m: dict, q=R.QUANT[None]):
    """(T, E) weights over all the router's experts: nought for an expert
    the token did not choose."""
    p = jax.nn.softmax(router_logits(x, lp, q), axis=-1)
    _, idx = lax.top_k(p, m["n_experts_per_tok"])
    w = p * jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)
    return w / jnp.sum(w, axis=-1, keepdims=True) if m["norm_topk_prob"] else w


def router_margin(x, lp, m: dict):
    """By how much of a router logit each token's last chosen expert (the
    10th) leads the first one left out (the 11th): what rounding has to
    move to flip the choice."""
    top, _ = lax.top_k(router_logits(x, lp), m["n_experts_per_tok"] + 1)
    return top[:, -2] - top[:, -1]


def _ffn(x, gate_up, down, q):
    g, u = jnp.split(_mm(x, gate_up, q), 2, axis=-1)
    return _mm(jax.nn.silu(g) * u, down, q)


def _moe(x, lp, m: dict, q):
    """The routed experts held here, and the shared expert under its gate."""
    first, held = m.get("experts_first", 0), m["experts_held"]
    w = router(x, lp, m, q)[:, first:first + held]

    def one(y, ew):
        gate_up, down, we = (a.astype(jnp.float32) for a in ew)
        return y + we[:, None] * _ffn(x, gate_up, down, q), None

    y, _ = lax.scan(one, jnp.zeros_like(x), (lp["experts_gate_up"], lp["experts_down"], w.T))
    gate = jax.nn.sigmoid(jnp.matmul(x, lp["shared_gate"], precision=R.HIGHEST))
    return y + gate * _ffn(x, lp["shared_gate_up"], lp["shared_down"], q)


def layer(x, lp, m: dict, kind: str, quant=None, margins: bool = False):
    """One layer on the stream x (T, C) of one sequence, float32; with
    `margins`, (x, each token's `router_margin`). `quant` is a rounding of
    `reference.QUANT` for every product's operands, or `state_bf16`: the
    products exact and S rounded to bfloat16 after every token."""
    q = R.QUANT.get(quant, R.QUANT[None])
    experts = {name: lp[name] for name in ROUTED}  # cast one expert at a time
    lp = {k: v.astype(jnp.float32) for k, v in lp.items() if k not in ROUTED} | experts
    eps = m["rms_norm_eps"]
    hn = _rms(x, 1.0 + lp["attn_norm"], eps)
    h = x + (_attention(hn, lp, m, q) if kind == "attn" else _gated_delta(hn, lp, m, q, quant))
    hn = _rms(h, 1.0 + lp["mlp_norm"], eps)
    y = h + _moe(hn, lp, m, q)
    return (y, router_margin(hn, lp, m)) if margins else y


def head(top, h, m: dict, quant=None):
    """Logits of the stream h (T, C)."""
    top = {k: top[k].astype(jnp.float32) for k in ("norm_f", "lm_head")}
    q = R.QUANT.get(quant, R.QUANT[None])
    return _mm(_rms(h, 1.0 + top["norm_f"], m["rms_norm_eps"]), top["lm_head"], q)


def forward_logits(params, tokens, m: dict, quant=None):
    """(T,) token ids of one sequence -> (T, V) logits, or (R, T) -> (R, T,
    V), from a whole parameter tree (the tests' size)."""
    tokens = jnp.asarray(tokens)
    if tokens.ndim == 2:
        return jax.vmap(lambda t: forward_logits(params, t, m, quant))(tokens)
    x = params["embed"][tokens].astype(jnp.float32)
    for i, kind in enumerate(_kinds(m)):
        x = layer(x, layer_of(params, i, m), m, kind, quant)
    return head(params, x, m, quant)


REFERENCE_PAD = 512  # sequences are padded to one multiple: one shape compiles
SERVED_PAD = 256  # and the served positions read of each

# A served token whose choice of experts hangs, in some layer of the
# reference, on less of a router logit than FRAGILE_MARGIN (the 10th chosen
# over the 11th) is judged apart, by the Xing4 family's `judge_tokens`
# (imported, not copied: its quantile and its least robust share are kept).
# The readings behind the margin: the cell's limits file.
FRAGILE_MARGIN = 0.005
_XING4 = manifest.load_family("xing4")  # loaded when first asked, as every family


def judge_tokens(gap, margin):
    """`families/xing4.py` `judge_tokens` on this family's margins, brought
    to its scale (they are router logits, its are sigmoid scores)."""
    scale = _XING4.FRAGILE_MARGIN / FRAGILE_MARGIN
    return _XING4.judge_tokens(gap, np.asarray(margin) * scale)


def token_gaps(m: dict, seed: int, samples, *, quant=None) -> list[dict]:
    """Per sample of (prompt ids, served ids), teacher forced, an array a
    served token: `gap`, by which its logit lies below the reference's best;
    `low_gap`, the same of the token `quant` puts first (the control; `gap`
    again without one); `margin`, the least `router_margin` of its position
    over the layers, and `layer`, where that is. One layer's weights are made
    from the seed, used on every sample's stream and dropped. Every sample is
    padded at its end to the longest's length (a causal model's earlier
    positions do not see it) and its served positions to the most served, so
    that each function compiles once."""
    t0 = time.monotonic()
    # The loop has dropped its engine, which its own jitted methods refer
    # back to: only the cycle collector frees it, and this reference needs
    # the room.
    gc.collect()
    held = jax.live_arrays()
    log(f"reference starts with {sum(a.nbytes for a in held) / 1e9:.2f} GB in {len(held)} live arrays")
    key = R.seed_key(seed)
    quants = (None,) if quant is None else (None, quant)
    top = jax.jit(lambda k: top_params(m, k))(key)
    seqs = [
        np.concatenate([np.asarray(p, np.int32), np.asarray(t, np.int32)])[: positions(m)]
        for p, t in samples
    ]
    # logits at position i predict token i+1: the served tokens sit at
    # positions len(prompt)-1 ... len(seq)-2.
    spans = [(len(p) - 1, seq.size - 1) for (p, _), seq in zip(samples, seqs)]
    length = -(-max(s.size for s in seqs) // REFERENCE_PAD) * REFERENCE_PAD
    rows = -(-max(max(hi - lo for lo, hi in spans), 1) // SERVED_PAD) * SERVED_PAD
    padded = np.zeros((len(seqs), length), np.int32)
    for j, seq in enumerate(seqs):
        padded[j, : seq.size] = seq
    start = jax.jit(lambda top, t: top["embed"][t].astype(jnp.float32))
    streams = {qn: [start(top, jnp.asarray(t)) for t in padded] for qn in quants}
    margins = [jnp.full((length,), jnp.inf)] * len(seqs)
    tightest = [jnp.zeros((length,), jnp.int32)] * len(seqs)  # the layer of each margin
    make = jax.jit(lambda k, i, kind: layer_params(m, k, i, kind), static_argnums=2)
    step = jax.jit(
        lambda x, lp, kind, qn: layer(x, lp, m, kind, qn, margins=True),
        static_argnums=(2, 3), donate_argnums=0,
    )
    for i, kind in enumerate(_kinds(m)):
        lp = make(key, i, kind)
        for qn in quants:
            out = [step(x, lp, kind, qn) for x in streams[qn]]
            streams[qn] = [x for x, _ in out]
            if qn is None:
                tightest = [jnp.where(b < a, i, t) for t, a, (_, b) in zip(tightest, margins, out)]
                margins = [jnp.minimum(a, b) for a, (_, b) in zip(margins, out)]
        del lp, out
    jax.block_until_ready(list(streams.values()))
    t_layers = time.monotonic() - t0

    @functools.partial(jax.jit, static_argnums=3)
    def logits_at(top, x, at, qn):
        return head(top, x[at], m, qn)

    tokens = []
    for j, ((lo, hi), seq) in enumerate(zip(spans, seqs)):
        n = max(hi - lo, 0)
        at = jnp.asarray(np.minimum(lo + np.arange(rows), length - 1))
        logits = logits_at(top, streams[None][j], at, None)[:n]
        best = jnp.max(logits, axis=-1)
        nxt = jnp.asarray(seq[lo + 1:lo + 1 + n])
        gap = best - jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        low_gap = gap
        if quant is not None:
            low = jnp.argmax(logits_at(top, streams[quant][j], at, quant)[:n], axis=-1)
            low_gap = best - jnp.take_along_axis(logits, low[:, None], axis=-1)[:, 0]
        tokens.append({"gap": np.asarray(gap), "low_gap": np.asarray(low_gap),
                       "margin": np.asarray(margins[j][at][:n]),
                       "layer": np.asarray(tightest[j][at][:n])})
    log(f"reference: {len(seqs)} sequences padded to {length}, {rows} served positions each; "
        f"layers {t_layers:.1f}s, head {time.monotonic() - t0 - t_layers:.1f}s")
    return tokens


def serve_gaps(m: dict, seed: int, samples, *, quant=None) -> dict:
    """`judge_tokens` of the served tokens of `samples` (`token_gaps`): the
    widest gap by which a served token's logit lies below the reference's
    best among the tokens that choose their experts robustly, or the
    quantile of the others' if that is larger; with `quant`, beside it the
    same of the token the lower precision puts first (the control), and a
    log line of the same with S rounded to bfloat16 after every token.
    Prints what a later choice of the margin needs."""
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
            f"{float(np.mean(gap > 0.05)):.4f} / {float(np.mean(gap > 0.1)):.4f}")
    if quant is not None and quant != "state_bf16" and gap.size:
        state = token_gaps(m, seed, samples, quant="state_bf16")
        low_s = np.concatenate([t["low_gap"] for t in state])
        log(f"reference with S rounded to bfloat16 after every token: judged "
            f"{judge_tokens(low_s, margin):.4f}, widest gap {float(low_s.max()):.4f}, "
            f"share over 0: {float(np.mean(low_s > 0)):.4f}")
    return {"widest_gap": judge_tokens(gap, margin), "widest_gap_low": judge_tokens(low, margin),
            "tokens": int(gap.size)}
