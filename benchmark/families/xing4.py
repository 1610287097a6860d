"""The Xing4.0 family (XingChen-AGI, `model_type: xing4_0`): the only file
of the benchmark that knows this architecture. A configuration's file
names it (`"family": "xing4"`), and `manifest.load_family` finds it by that
name.

It holds the program's module built from a configuration's `model` group,
the weights from `--seed`, the plain reference, and the operations and bytes
worked out from shapes. The family trains in no cell: no training functions.

The plain reference is the forward pass of the equations in
`tpuflow/models/xing4.py`'s docstring in straightforward `jax.numpy`,
float32 at `highest` matmul precision: full heads everywhere (no
absorption, no cache, no pages), the routed experts as a dense sum over all
of them, each weighted by what the router gave the token (nought where it
was not chosen). It takes nothing the program has made but the weights'
recipe. Weights: bfloat16, every layer keyed by its index, so that the
program makes the whole tree in one jitted call (`make_params`, the stack
by `lax.map`: one layer's temporaries at a time) and the reference makes one
layer at a time inside its layer loop and drops it (`serve_gaps`): 5.54 B
parameters do not fit one chip in float32.
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

from benchmark.harness import reference as R
from benchmark.harness.runner import log

# The scopes inside a layer and after it: what `decode_carry_share.serve`
# leaves out, and the labels of the tables (PERF.md section 3).
BLOCK_SCOPES = (
    "mhc", "mla_q", "kv_write", "kv_read", "kv_expand", "attn_core", "mla_out",
    "router", "moe_experts", "moe_shared", "mlp", "lm_head", "sample",
)
MODULE_SCOPES = ("layer", "layers", "Xing4")  # flax's own, around everything

# The published widths (the configuration's top-level keys) and the key of
# the `model` group each has to equal.
PUBLISHED = {
    "hidden_size": "hidden_size", "num_attention_heads": "n_head",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "intermediate_size": "intermediate_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "n_routed_experts": "n_routed_experts", "num_experts_per_tok": "n_experts_per_tok",
    "n_shared_experts": "n_shared_experts", "vocab_size": "vocab_size",
    "hc_mult": "hc_mult", "hc_sinkhorn_iters": "hc_sinkhorn_iters",
    "routed_scaling_factor": "routed_scaling_factor", "rms_norm_eps": "rms_norm_eps",
}


# ------------------------------------------------------------ the program
def module(m: dict):
    """The program's flax module for a configuration's `model` group."""
    from tpuflow.models.xing4 import Xing4, Xing4Config

    fields = dict(m)
    fields["dtype"] = jnp.dtype(fields.get("dtype", "bfloat16"))
    return Xing4(Xing4Config(**fields))


def positions(m: dict) -> int:
    """The longest sequence a request may reach."""
    return m["n_ctx"]


def vocabulary(m: dict) -> int:
    """The ids the traffic may draw."""
    return m["vocab_size"]


def check_config(cfg: dict) -> list[str]:
    """Problems with a configuration's file (empty = none): every width
    as published, the depth and the positions the file says it reduced
    to, and the parameter count the file states."""
    bad = []
    m = cfg["model"]
    for key, mine in PUBLISHED.items():
        if m[mine] != cfg[key]:
            bad.append(f"model.{mine} {m[mine]} is not the published {key} {cfg[key]}")
    rope = cfg["rope_scaling"]
    for key, mine in (("factor", "rope_factor"), ("beta_fast", "rope_beta_fast"),
                      ("beta_slow", "rope_beta_slow"),
                      ("original_max_position_embeddings", "rope_original_max")):
        if m[mine] != rope[key]:
            bad.append(f"model.{mine} {m[mine]} is not rope_scaling.{key} {rope[key]}")
    served = cfg["served"]
    for key, mine in (("num_hidden_layers", "n_layer"), ("first_k_dense_replace", "first_k_dense"),
                      ("num_nextn_predict_layers", "n_mtp"), ("max_position_embeddings", "n_ctx")):
        if m[mine] != served[key]:
            bad.append(f"model.{mine} {m[mine]} is not served.{key} {served[key]}")
        if key not in cfg["reduced"] and served[key] != cfg[key]:
            bad.append(f"served.{key} differs from the source and is not in reduced")
    if n_params(m) != cfg["parameters"]:
        bad.append(f"parameters {cfg['parameters']} is not the {n_params(m)} of the shapes")
    return bad


def test_config() -> dict:
    """A configuration at the `test` width for the CPU tests, with the
    limit of `correct` at that size (float32 program against the float32
    reference: rounding alone, 1e-4 of a logit; the float8 control reads
    0.05 to 0.3 there)."""
    return {
        "model": {
            "vocab_size": 256, "n_ctx": 128, "hidden_size": 64, "n_layer": 3,
            "first_k_dense": 1, "n_head": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
            "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
            "intermediate_size": 128, "moe_intermediate_size": 32,
            "n_routed_experts": 8, "n_experts_per_tok": 2, "n_shared_experts": 1,
            "routed_scaling_factor": 2.0, "rms_norm_eps": 1e-6, "hc_mult": 4,
            "hc_sinkhorn_iters": 20, "rope_factor": 64.0, "rope_original_max": 32,
            "rope_beta_fast": 32.0, "rope_beta_slow": 1.0, "n_mtp": 0,
            "attn_impl": "auto", "dtype": "float32",
        },
        "serve": {"max_slots": 4, "paged": True, "prefix_cache": True, "speculative": 0,
                  "quant": None, "decode_block": 4},
        "limits": {"serve": {"widest_logit_gap": 1e-3}},
    }


# ------------------------------- operations and bytes worked out from shapes
def _layer_shapes(m: dict, moe: bool) -> dict[str, tuple[int, ...]]:
    """Every leaf of one layer, by the name the program's module gives it."""
    c, h = m["hidden_size"], m["n_head"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    rq, rk, n = m["q_lora_rank"], m["kv_lora_rank"], m["hc_mult"]
    hc = 2 * n + n * n
    out = {
        "attn_norm": (c,), "q_a": (c, rq), "q_norm": (rq,), "q_b": (rq, h * (dn + dr)),
        "kv_a": (c, rk + dr), "kv_norm": (rk,), "kv_b": (rk, h * (dn + dv)),
        "o": (h * dv, c), "mlp_norm": (c,),
    }
    for sub in ("attn", "mlp"):
        out[f"hc_{sub}_phi"] = (n * c, hc)
        out[f"hc_{sub}_alpha"] = (3,)
        out[f"hc_{sub}_b"] = (hc,)
    if moe:
        e, f = m["n_routed_experts"], m["moe_intermediate_size"]
        fs = f * m["n_shared_experts"]
        out.update({
            "router": (c, e), "router_bias": (e,), "experts_gate_up": (e, c, 2 * f),
            "experts_down": (e, f, c), "shared_gate_up": (c, 2 * fs), "shared_down": (fs, c),
        })
    else:
        f = m["intermediate_size"]
        out.update({"mlp_gate_up": (c, 2 * f), "mlp_down": (f, c)})
    return out


def _count(shapes: dict, skip=()) -> int:
    return sum(math.prod(s) for k, s in shapes.items() if k not in skip)


ROUTED = ("experts_gate_up", "experts_down")


def n_params(m: dict) -> int:
    """Parameters held: embedding, untied head, final norm, the dense and
    the expert layers (every expert), and the multi-token module if the
    configuration loads it."""
    c, v = m["hidden_size"], m["vocab_size"]
    dense, moe = _count(_layer_shapes(m, False)), _count(_layer_shapes(m, True))
    k = m["first_k_dense"]
    mtp = m.get("n_mtp", 0) * (moe + 2 * c + 2 * c * c)
    return 2 * v * c + c + k * dense + (m["n_layer"] - k) * moe + mtp


def _routed_expert_params(m: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _resident_params(m: dict) -> int:
    """What every step reads whatever is routed: everything but the
    embedding table (a row a token) and the routed experts."""
    c, v, k = m["hidden_size"], m["vocab_size"], m["first_k_dense"]
    dense = _count(_layer_shapes(m, False))
    moe = _count(_layer_shapes(m, True), skip=ROUTED)
    return v * c + c + k * dense + (m["n_layer"] - k) * moe


def routed_experts_held(m: dict) -> int:
    """Routed experts over all layers: the most a step can touch."""
    return (m["n_layer"] - m["first_k_dense"]) * m["n_routed_experts"]


def forward_flops_per_token(m: dict) -> float:
    """2 x the parameters a token uses: everything resident and
    `n_experts_per_tok` routed experts a layer, not all of them. The
    attention-score products are not counted (the share reads low)."""
    routed = (m["n_layer"] - m["first_k_dense"]) * m["n_experts_per_tok"]
    return 2.0 * (_resident_params(m) + routed * _routed_expert_params(m))


def train_flops_per_token(m: dict) -> float:
    """Forward and backward, three times the forward (no cell trains this
    family: `mfu.train` would ask)."""
    return 3.0 * forward_flops_per_token(m)


def attention_flops(m: dict, batch: int, seq: int, steps: float) -> float:
    """The least causal attention with full heads has to do in `steps`
    training steps (scores over 192, values over 128, half under the mask,
    three times with the backward pass)."""
    widths = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    per_layer = 3 * 0.5 * 2.0 * batch * m["n_head"] * seq * seq * widths
    return steps * m["n_layer"] * per_layer


def moe_step_bytes(m: dict, experts_touched: float, weight_bytes: int = 2) -> float:
    """Bytes the routed-expert products have to read for `experts_touched`
    (distinct experts, summed over layers and steps): each one's three
    matrices once."""
    return float(experts_touched * _routed_expert_params(m) * weight_bytes)


def decode_step_bytes(m: dict, live_context_tokens: int, weight_bytes: int = 2,
                      cache_bytes: int = 2) -> float:
    """The least one decode step has to read: everything resident once,
    the routed experts one live row touches (`n_experts_per_tok` a layer:
    the reader is not told how many rows are live, and every further row
    only adds), and the latents of the live context once."""
    moe_layers = m["n_layer"] - m["first_k_dense"]
    latent = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    return float(
        _resident_params(m) * weight_bytes
        + moe_step_bytes(m, moe_layers * m["n_experts_per_tok"], weight_bytes)
        + m["n_layer"] * live_context_tokens * latent * cache_bytes
    )


# -------------------------------------------------- weights from `--seed`
OUT_PROJECTIONS = ("o", "mlp_down", "experts_down", "shared_down")
PUBLISHED_HIDDEN = 3584
TOP_KEY, MTP_KEY = 1_000_000, 2_000_000  # fold-ins beside the layers' indices


def _leaf(key, name: str, shape, m: dict):
    """One leaf, bfloat16-rounded values in the model's dtype. Sizes: 0.02
    for matrices (the out-projections 0.02 / sqrt(2 L)); the embedding 1,
    so that a sub-layer's output (0.1 to 0.25 at these sizes) is a
    fraction of the stream it is added to, as in a trained model: at 0.02
    the stream is its last sub-layers' outputs and one flipped expert moves
    a logit as far as float8 rounding does; norms around 1;
    the selection bias 0.1; the hyper-connection's φ 0.01 (x̃ φ then has a
    spread of about 1.2 at the published width), α around 1, b_pre and
    b_post 0.5, b_res 2 on the diagonal and 0.3 off it: coefficients that
    vary from token to token around a mixing that keeps most of a stream."""
    n = m["hc_mult"]
    z = jax.random.normal(key, shape, jnp.float32)
    # Matrices are sized for the published width; a narrower model (the
    # tests') gets them larger by the root of the ratio, so that its
    # sub-layers weigh as much against its stream as the cell's do.
    wide = math.sqrt(PUBLISHED_HIDDEN / m["hidden_size"])
    if "norm" in name:
        val = 1.0 + 0.02 * z
    elif name.endswith("_alpha"):
        val = 1.0 + 0.1 * z
    elif name.startswith("hc_") and name.endswith("_b"):
        eye = jnp.concatenate([jnp.zeros(2 * n), 2.0 * jnp.eye(n).reshape(-1)])
        spread = jnp.concatenate([jnp.full(2 * n, 0.5), jnp.full(n * n, 0.3)])
        val = eye + spread * z
    elif name.endswith("_phi"):
        val = 0.01 * wide * z
    elif name == "router_bias":
        val = 0.1 * z
    elif name in OUT_PROJECTIONS:
        val = 0.02 * wide / math.sqrt(2 * m["n_layer"]) * z
    elif name == "embed":
        val = z
    else:
        val = 0.02 * wide * z
    return val.astype(jnp.bfloat16).astype(jnp.dtype(m.get("dtype", "bfloat16")))


def layer_params(m: dict, key, index, moe: bool) -> dict:
    """Layer `index`'s leaves (call under `jit`; `index` may be traced)."""
    k = jax.random.fold_in(key, index)
    return {
        name: _leaf(jax.random.fold_in(k, j), name, shape, m)
        for j, (name, shape) in enumerate(sorted(_layer_shapes(m, moe).items()))
    }


def top_params(m: dict, key) -> dict:
    c, v = m["hidden_size"], m["vocab_size"]
    k = jax.random.fold_in(key, TOP_KEY)
    shapes = {"embed": (v, c), "norm_f": (c,), "lm_head": (c, v)}
    if m.get("n_mtp", 0):
        shapes.update({"mtp_hnorm": (c,), "mtp_enorm": (c,), "mtp_proj": (2 * c, c)})
    return {
        name: _leaf(jax.random.fold_in(k, j), name, shape, m)
        for j, (name, shape) in enumerate(sorted(shapes.items()))
    }


def make_params(m: dict, key) -> dict:
    """The whole parameter tree (call under `jit`), as the program's module
    declares it: the routed layers stacked, their experts in leaves of
    their own beside the stack."""
    tree = top_params(m, key)
    k = m["first_k_dense"]
    for i in range(k):
        tree[f"dense_{i}"] = layer_params(m, key, i, False)
    if m["n_layer"] > k:
        stack = lax.map(lambda i: layer_params(m, key, i, True), jnp.arange(k, m["n_layer"]))
        for name in ROUTED:
            tree[name] = stack.pop(name)
        tree["layers"] = {"layer": stack}
    if m.get("n_mtp", 0):
        tree["mtp_layer"] = layer_params(m, key, MTP_KEY, True)
        for name in ROUTED:
            tree["mtp_" + name] = tree["mtp_layer"].pop(name)[None]
    return tree


def layer_of(params, i: int, m: dict) -> dict:
    """Layer `i`'s leaves out of a whole tree, experts included."""
    k = m["first_k_dense"]
    if i < k:
        return params[f"dense_{i}"]
    lp = jax.tree_util.tree_map(lambda a: a[i - k], params["layers"]["layer"])
    return {**lp, **{name: params[name][i - k] for name in ROUTED}}


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
    """No cell trains this family (its training state does not fit one
    chip at the guide's floors: PERF.md section 4), so no reference of a
    training step was written: say so where one is asked for."""
    raise NotImplementedError(
        "the xing4 family trains in no cell: its reference is the forward pass alone"
    )


# ---------------------------------------------------- the plain reference
def _mm(a, b, q):
    return jnp.matmul(q(a), q(b), precision=R.HIGHEST)


def _rms(x, scale, eps):
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y if scale is None else y * scale


def _yarn_inv_freq(m: dict):
    dim, theta = m["qk_rope_head_dim"], m.get("rope_theta", 10000.0)
    factor, orig = m["rope_factor"], m["rope_original_max"]
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(m["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(m["rope_beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return jnp.asarray(extra / factor * ramp + extra * (1 - ramp), jnp.float32)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope(x, m: dict):
    """Rotate consecutive pairs of (T, ..., dim) by the position's angles."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * _yarn_inv_freq(m)
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (-1,))
    # The factor on cos and sin: the ratio of the two YaRN scales.
    f = _mscale(m["rope_factor"], m.get("rope_mscale", 1.0)) / _mscale(
        m["rope_factor"], m.get("rope_mscale_all_dim", 1.0))
    pair = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pair[..., 0], pair[..., 1]
    cos, sin = jnp.cos(ang) * f, jnp.sin(ang) * f
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _attention(x, lp, m: dict, q):
    t = x.shape[0]
    h, dn, dr, dv = m["n_head"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    r, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    cq = _rms(_mm(x, lp["q_a"], q), lp["q_norm"], eps)
    qh = _mm(cq, lp["q_b"], q).reshape(t, h, dn + dr)
    q_nope, q_rope = qh[..., :dn], _rope(qh[..., dn:], m)
    kv = _mm(x, lp["kv_a"], q)
    c_kv, k_rope = _rms(kv[:, :r], lp["kv_norm"], eps), _rope(kv[:, r:], m)
    kvh = _mm(c_kv, lp["kv_b"], q).reshape(t, h, dn + dv)
    k_nope, v = kvh[..., :dn], kvh[..., dn:]
    ms = _mscale(m["rope_factor"], m.get("rope_mscale_all_dim", 1.0))
    s = jnp.einsum("qhd,khd->hqk", q(q_nope), q(k_nope), precision=R.HIGHEST)
    s = s + jnp.einsum("qhd,kd->hqk", q(q_rope), q(k_rope), precision=R.HIGHEST)
    s = s * ((dn + dr) ** -0.5 * ms * ms)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", q(p), q(v), precision=R.HIGHEST)
    return _mm(a.reshape(t, h * dv), lp["o"], q)


def _ffn(x, w_gate_up, w_down, q):
    g, u = jnp.split(_mm(x, w_gate_up, q), 2, axis=-1)
    return _mm(jax.nn.silu(g) * u, w_down, q)


def router(x, lp, m: dict, q=R.QUANT[None]):
    """(T, E) weights: nought for an expert the token did not choose."""
    s = jax.nn.sigmoid(_mm(x, lp["router"], q))
    _, idx = lax.top_k(s + lp["router_bias"], m["n_experts_per_tok"])
    chosen = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)
    w = s * chosen
    return w / jnp.sum(w, axis=-1, keepdims=True) * m["routed_scaling_factor"]


def router_margin(x, lp, m: dict):
    """By how much of a score `s + b` each token's last chosen expert
    leads the first one left out: what rounding has to move to flip the
    choice."""
    s = jax.nn.sigmoid(_mm(x, lp["router"], R.QUANT[None])) + lp["router_bias"]
    top, _ = lax.top_k(s, m["n_experts_per_tok"] + 1)
    return top[:, -2] - top[:, -1]


def _moe(x, lp, m: dict, q):
    w = router(x, lp, m, q)

    def one(y, ew):
        gate_up, down, we = ew
        return y + we[:, None] * _ffn(x, gate_up, down, q), None

    y, _ = lax.scan(one, jnp.zeros_like(x), (lp["experts_gate_up"], lp["experts_down"], w.T))
    return y + _ffn(x, lp["shared_gate_up"], lp["shared_down"], q)


def hc_coefficients(X, phi, alpha, b, m: dict, q=R.QUANT[None]):
    """(H_pre, H_post, H_res) of the streams X (T, n, C)."""
    n = m["hc_mult"]
    raw = _mm(_rms(X.reshape(X.shape[0], -1), None, m["rms_norm_eps"]), phi, q)
    pre = jax.nn.sigmoid(alpha[0] * raw[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(alpha[2] * raw[:, 2 * n:] + b[2 * n:], -30.0, 30.0))
    res = res.reshape(-1, n, n)
    for _ in range(m["hc_sinkhorn_iters"]):
        res = res / (jnp.sum(res, axis=-1, keepdims=True) + m.get("hc_eps", 1e-6))
        res = res / (jnp.sum(res, axis=-2, keepdims=True) + m.get("hc_eps", 1e-6))
    return pre, post, res


def _hc(X, lp, name: str, sub, m: dict, q):
    pre, post, res = hc_coefficients(
        X, lp[f"hc_{name}_phi"], lp[f"hc_{name}_alpha"], lp[f"hc_{name}_b"], m, q)
    x = _rms(jnp.einsum("tn,tnc->tc", pre, X), lp[f"{name}_norm"], m["rms_norm_eps"])
    return jnp.einsum("tnm,tmc->tnc", res, X) + post[:, :, None] * sub(x)[:, None, :]


def layer(X, lp, m: dict, moe: bool, quant=None, margins: bool = False):
    """One layer on the streams X (T, n, C) of one sequence, float32; with
    `margins`, (X, each token's `router_margin`: infinite in a dense layer)."""
    q = R.QUANT[quant]
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    X = _hc(X, lp, "attn", lambda x: _attention(x, lp, m, q), m, q)
    margin = [jnp.full((X.shape[0],), jnp.inf)]

    def ffn(x):
        if not moe:
            return _ffn(x, lp["mlp_gate_up"], lp["mlp_down"], q)
        margin[0] = router_margin(x, lp, m)
        return _moe(x, lp, m, q)

    X = _hc(X, lp, "mlp", ffn, m, q)
    return (X, margin[0]) if margins else X


def embed(top, tokens, m: dict):
    x = top["embed"][tokens].astype(jnp.float32)
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], m["hc_mult"], x.shape[1]))


def head(top, h, m: dict, quant=None):
    """Logits of the summed stream h (T, C)."""
    top = {k: top[k].astype(jnp.float32) for k in ("norm_f", "lm_head")}
    return _mm(_rms(h, top["norm_f"], m["rms_norm_eps"]), top["lm_head"], R.QUANT[quant])


def forward_logits(params, tokens, m: dict, quant=None, mtp: bool = False):
    """(T,) token ids of one sequence -> (T, V) logits, or (R, T) -> (R,
    T, V), from a whole parameter tree (the tests' size); with `mtp`, the
    multi-token module's logits beside them."""
    tokens = jnp.asarray(tokens)
    if tokens.ndim == 2:
        return jax.vmap(lambda t: forward_logits(params, t, m, quant, mtp))(tokens)
    X = embed(params, tokens, m)
    k = m["first_k_dense"]
    for i in range(m["n_layer"]):
        X = layer(X, layer_of(params, i, m), m, i >= k, quant)
    h = jnp.sum(X, axis=1)
    logits = head(params, h, m, quant)
    if not mtp:
        return logits
    q, eps = R.QUANT[quant], m["rms_norm_eps"]
    f32 = lambda name: params[name].astype(jnp.float32)  # noqa: E731
    nxt = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    joined = jnp.concatenate([
        _rms(h, f32("mtp_hnorm"), eps),
        _rms(f32("embed")[nxt], f32("mtp_enorm"), eps),
    ], axis=-1)
    x = _mm(joined, f32("mtp_proj"), q)
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], m["hc_mult"], x.shape[1]))
    lp = {**params["mtp_layer"], **{name: params["mtp_" + name][0] for name in ROUTED}}
    X = layer(X, lp, m, True, quant)
    return logits, head(params, jnp.sum(X, axis=1), m, quant)


REFERENCE_PAD = 512  # sequences are padded to one multiple: one shape compiles
SERVED_PAD = 256  # and the served positions read of each

# A served token whose choice of experts hangs, in some routed layer of the
# reference, on less of a score than FRAGILE_MARGIN is judged apart: rounding
# decides such a choice, in the program as in any other arithmetic, and one
# flipped expert moves a logit further than any product's rounding does.
# `judge_tokens` holds the others to the widest gap and these to a quantile.
# The readings behind the three numbers: the cell's limits file.
FRAGILE_MARGIN = 0.005
FRAGILE_QUANTILE = 0.95  # of the fragile tokens' gaps, held to the same limit
ROBUST_SHARE_MIN = 0.2   # with a smaller share of robust tokens, all are held to the widest gap


def judge_tokens(gap, margin):
    """The one number of `gap`s (an array a served token) that the limit
    holds, or None where there is no token. The tokens of a `margin` of at
    least `FRAGILE_MARGIN` give the widest of their gaps. The others, whose
    choice of experts rounding may flip, give the `FRAGILE_QUANTILE` of
    theirs (an actual token's gap): bfloat16 flips one or two in a hundred of
    them, float8 one in six. The larger of the two is the number. Where
    fewer than `ROBUST_SHARE_MIN` of the tokens are robust, nothing stands
    for the model's arithmetic and every token is held to the widest gap."""
    gap, margin = np.asarray(gap), np.asarray(margin)
    if not gap.size:
        return None
    robust = margin >= FRAGILE_MARGIN
    if robust.mean() < ROBUST_SHARE_MIN:
        return float(gap.max())
    return max(float(gap[robust].max()), _fragile_quantile(gap[~robust]))


def _fragile_quantile(gap) -> float:
    return float(np.quantile(gap, FRAGILE_QUANTILE, method="higher")) if gap.size else 0.0


def token_gaps(m: dict, seed: int, samples, *, quant=None) -> list[dict]:
    """Per sample of (prompt ids, served ids), teacher forced, an array a
    served token: `gap`, by which its logit lies below the reference's
    best; `low_gap`, the same of the token `quant` puts first (the control;
    `gap` again without one); `margin`, the least `router_margin` of its
    position over the routed layers, and `layer`, where that is. One layer's weights are made from the
    seed, used on every sample's streams and dropped. Every sample is
    padded to the longest's length and its served positions to the most
    served, so that each function compiles once."""
    t0 = time.monotonic()
    # The loop has dropped its engine, which its own jitted methods refer
    # back to: only the cycle collector frees it, and this reference needs
    # the room (12.1 GB of the engine were still live here on the chip:
    # PERF.md section 6, PR 34).
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
    start = jax.jit(lambda top, t: embed(top, t, m))
    streams = {qn: [start(top, jnp.asarray(t)) for t in padded] for qn in quants}
    margins = [jnp.full((length,), jnp.inf)] * len(seqs)
    tightest = [jnp.zeros((length,), jnp.int32)] * len(seqs)  # the layer of each margin
    make = jax.jit(lambda k, i, moe: layer_params(m, k, i, moe), static_argnums=2)
    step = jax.jit(
        lambda X, lp, moe, qn: layer(X, lp, m, moe, qn, margins=True),
        static_argnums=(2, 3), donate_argnums=0,
    )
    for i in range(m["n_layer"]):
        moe = i >= m["first_k_dense"]
        lp = make(key, i, moe)
        for qn in quants:
            out = [step(X, lp, moe, qn) for X in streams[qn]]
            streams[qn] = [X for X, _ in out]
            if qn is None:
                tightest = [jnp.where(b < a, i, t) for t, a, (_, b) in zip(tightest, margins, out)]
                margins = [jnp.minimum(a, b) for a, (_, b) in zip(margins, out)]
        del lp, out
    jax.block_until_ready(list(streams.values()))
    t_layers = time.monotonic() - t0

    @functools.partial(jax.jit, static_argnums=3)
    def logits_at(top, X, at, qn):
        return head(top, jnp.sum(X[at], axis=1), m, qn)

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
    `FRAGILE_QUANTILE` of the others' if that is larger; with `quant`,
    beside it the same of the token the lower precision puts first (the
    control). Prints what a later choice of the three constants needs."""
    tokens = token_gaps(m, seed, samples, quant=quant)
    gap, low, margin, where = (
        np.concatenate([t[k] for t in tokens]) if tokens else np.zeros(0)
        for k in ("gap", "low_gap", "margin", "layer")
    )
    if gap.size:
        robust = margin >= FRAGILE_MARGIN
        widest = lambda a: float(a.max()) if a.size else 0.0  # noqa: E731
        over = lambda a: float(np.mean(a > 0.1)) if a.size else 0.0  # noqa: E731
        log(f"reference: {gap.size} served tokens, {int(robust.sum())} choose their experts by "
            f"{FRAGILE_MARGIN} of a score or more: widest gap of those {widest(gap[robust]):.4f}, of all "
            f"{widest(gap):.4f}; of the {int((~robust).sum())} others {over(gap[~robust]):.4%} lie over 0.1, "
            f"their {FRAGILE_QUANTILE:.0%} quantile {_fragile_quantile(gap[~robust]):.4f}; "
            "widest gap from a margin of 0.002 / 0.003 / 0.004: "
            + " / ".join(f"{widest(gap[margin >= t]):.4f}" for t in (0.002, 0.003, 0.004))
            + "; tokens over 0.1 by request "
            + " ".join(str(int(np.sum(t["gap"] > 0.1))) for t in tokens)
            + ", by the layer of their least margin "
            + " ".join(f"{int(i)}:{int(np.sum(where[gap > 0.1] == i))}" for i in np.unique(where[gap > 0.1])))
    return {"widest_gap": judge_tokens(gap, margin), "widest_gap_low": judge_tokens(low, margin),
            "tokens": int(gap.size)}
