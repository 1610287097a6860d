"""The Olmo-Hybrid family (allenai, `model_type: olmo_hybrid`): the only file
of the benchmark that knows this architecture. A configuration's file names
it (`"family": "olmo_hybrid"`), and `manifest.load_family` finds it by that
name.

It holds the program's module built from a configuration's `model` group,
the weights from `--seed`, the plain reference, and the operations and bytes
worked out from shapes. The family trains in no cell: no training functions.

The plain reference is the forward pass of the equations in
`tpuflow/models/olmo_hybrid.py`'s docstring in straightforward `jax.numpy`,
float32 at `highest` matmul precision, teacher forced over one sequence: the
post-norm block, the Gated DeltaNet's state S moved on token by token
exactly as written with `β = 2 sigmoid(b)` (a scan over the sequence: no
chunks, no cache, no slot rows), full causal softmax attention with its
QK-norm over the whole projection and no rotary embedding, the dense
SwiGLU. It takes nothing the program has made but the weights' recipe; of
the Qwen3-Next family it borrows the plain products, norms and rotary
(`_mm`, `_rms`, `_l2`, `_rope`). Weights: bfloat16, every layer keyed by its
index, so that the program makes the whole tree in one jitted call
(`make_params`) and the reference makes one layer at a time inside its layer
loop and drops it (`token_gaps`).
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

_Q = manifest.load_family("qwen3_next")  # loaded when first asked, as every family

# The scopes inside a layer and after it: what `decode_carry_share.serve`
# leaves out, and the labels of the tables (PERF.md section 3). `sample` is
# the engine's.
BLOCK_SCOPES = (
    "gdn_in", "gdn_conv", "gdn_core", "gdn_out", "attn_q", "kv_write", "kv_read",
    "attn_core", "attn_out", "ffn", "lm_head", "sample",
)
MODULE_SCOPES = ("layer", "gdn", "attn", "periods", "OlmoHybrid")  # flax's own

# The published widths (the configuration's top-level keys) and the key of
# the `model` group each has to equal.
PUBLISHED = {
    "vocab_size": "vocab_size", "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_attention_heads": "n_head", "num_key_value_heads": "n_kv_head",
    "linear_num_key_heads": "linear_num_key_heads",
    "linear_num_value_heads": "linear_num_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel_dim", "rms_norm_eps": "rms_norm_eps",
}
# Cut here, each key at the top level the number served (the `published`
# group keeps the source's): depth and positions.
SERVED = {"num_hidden_layers": "n_layer", "max_position_embeddings": "n_ctx"}
# What the published config states of the mechanism, and what the model
# group has to choose for it.
CHOICES = {"block_norm": "post", "norm_offset": 0.0, "ffn": "dense", "attn_gate": False,
           "qk_norm": "proj", "partial_rotary_factor": 0.0, "gdn_beta_scale": 2.0}


# ------------------------------------------------------------ the program
def module(m: dict):
    """The program's flax module for a configuration's `model` group. A
    program from before the model was written cannot run the family's
    cells: it is told so at once, before anything is built."""
    try:
        from tpuflow.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig
    except ImportError as e:
        raise manifest.ManifestError(
            f"family 'olmo_hybrid' needs tpuflow/models/olmo_hybrid.py, which this program lacks ({e})"
        ) from e

    fields = dict(m)
    fields["dtype"] = jnp.dtype(fields.get("dtype", "bfloat16"))
    return OlmoHybrid(OlmoHybridConfig(**fields))


def positions(m: dict) -> int:
    """The longest sequence a request may reach."""
    return m["n_ctx"]


def vocabulary(m: dict) -> int:
    return m["vocab_size"]


def _kinds(m: dict) -> list[str]:
    """Layer i's kind: `gdn`, or `attn` every `full_attention_interval`-th."""
    n = m["full_attention_interval"]
    return ["attn" if (i + 1) % n == 0 else "gdn" for i in range(m["n_layer"])]


def check_config(cfg: dict) -> list[str]:
    """Problems with a configuration's file (empty = none): every width as
    published, the head size the attention's width gives, each cut the file
    lists and reduces to, `layer_types` the same whole periods as the
    model, the mechanism's choices, and both parameter counts it states
    (the published model's and what this chip holds)."""
    bad = []
    m = cfg["model"]
    for key, mine in PUBLISHED.items():
        if m[mine] != cfg[key]:
            bad.append(f"model.{mine} {m[mine]} is not the published {key} {cfg[key]}")
    if m["n_head"] * m["head_dim"] != cfg["hidden_size"]:
        bad.append(f"{m['n_head']} heads of {m['head_dim']} are not the hidden size {cfg['hidden_size']}")
    published = cfg["published"]
    for key, mine in SERVED.items():
        if m[mine] != cfg[key]:
            bad.append(f"model.{mine} {m[mine]} is not the {key} {cfg[key]} served")
        if key not in cfg["reduced"] and published[key] != cfg[key]:
            bad.append(f"{key} differs from the source and is not in reduced")
    kinds = ["attn" if t == "full_attention" else "gdn" for t in cfg["layer_types"]]
    if kinds != _kinds(m):
        bad.append("layer_types is not the model's periods of three Gated DeltaNet layers and one attention")
    if published["layer_types"][:len(kinds)] != cfg["layer_types"]:
        bad.append("layer_types is not the published pattern cut to the layers served")
    if m["n_layer"] % m["full_attention_interval"]:
        bad.append(f"{m['n_layer']} layers are not whole periods of {m['full_attention_interval']}")
    for key, want in CHOICES.items():
        if m.get(key) != want:
            bad.append(f"model.{key} {m.get(key)!r} is not the published mechanism's {want!r}")
    if cfg["linear_allow_neg_eigval"] is not True or cfg["rope_parameters"].get("rope_theta") is not None:
        bad.append("the published config allows negative eigenvalues and names no rope_theta")
    if n_params(m) != cfg["parameters"]:
        bad.append(f"parameters {cfg['parameters']} is not the {n_params(m)} of the shapes")
    whole = dict(m, n_layer=published["num_hidden_layers"])
    if n_params(whole) != cfg["parameters_published"]:
        bad.append(f"parameters_published {cfg['parameters_published']} is not the "
                   f"{n_params(whole)} of the published shapes")
    return bad


def test_config() -> dict:
    """A configuration at the `test` width for the CPU tests, with the
    limit of `correct` at that size (float32 program against the float32
    reference: the order of the sums alone, 1e-5 of a logit; the float8
    control reads 1e-2 and more there). Two periods; keys of 16 and values
    of 24, wider than deep as the cell's; chunks of 8 so that a prompt
    spans several."""
    return {
        "model": {
            "vocab_size": 256, "n_ctx": 128, "hidden_size": 64, "n_layer": 8,
            "full_attention_interval": 4, "n_head": 4, "n_kv_head": 4, "head_dim": 16,
            "linear_num_key_heads": 3, "linear_num_value_heads": 3,
            "linear_key_head_dim": 16, "linear_value_head_dim": 24,
            "linear_conv_kernel_dim": 4, "intermediate_size": 96, **CHOICES,
            "rms_norm_eps": 1e-6, "gdn_chunk": 8, "dtype": "float32",
        },
        "serve": {"max_slots": 4, "paged": True, "prefix_cache": True, "speculative": 0,
                  "quant": None, "decode_block": 4},
        "limits": {"serve": {"widest_logit_gap": 1e-3}},
    }


# ------------------------------- operations and bytes worked out from shapes
FFN = ("gate_up", "down")


def _layer_shapes(m: dict, kind: str) -> dict[str, tuple[int, ...]]:
    """Every leaf of one layer, by the name the program's module gives it."""
    c, f = m["hidden_size"], m["intermediate_size"]
    out = {"attn_norm": (c,), "mlp_norm": (c,), "gate_up": (c, 2 * f), "down": (f, c)}
    if kind == "attn":
        h, g, d = m["n_head"], m["n_kv_head"], m["head_dim"]
        out.update({"q": (c, h * d), "k": (c, g * d), "v": (c, g * d),
                    "q_norm": (h * d,), "k_norm": (g * d,), "o": (h * d, c)})
    else:
        hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
        dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
        conv = 2 * hk * dk + hv * dv
        out.update({"in_qkvz": (c, conv + hv * dv), "in_ba": (c, 2 * hv),
                    "conv": (m["linear_conv_kernel_dim"], conv), "A_log": (hv,),
                    "dt_bias": (hv,), "out_norm": (dv,), "out": (hv * dv, c)})
    return out


def _count(shapes: dict, keep=None) -> int:
    return sum(math.prod(s) for k, s in shapes.items() if keep is None or k in keep)


def n_params(m: dict) -> int:
    """Parameters held: embedding, untied head, final norm, every layer."""
    c, v = m["hidden_size"], m["vocab_size"]
    return 2 * v * c + c + sum(_count(_layer_shapes(m, k)) for k in _kinds(m))


def forward_flops_per_token(m: dict) -> float:
    """2 x the parameters a token uses: every one but the embedding table
    (a row a token). The attention-score products and the delta rule's own
    arithmetic are not counted (the share reads low)."""
    return 2.0 * (n_params(m) - m["vocab_size"] * m["hidden_size"])


def train_flops_per_token(m: dict) -> float:
    """Forward and backward, three times the forward (no cell trains this
    family: `mfu.train` would ask)."""
    return 3.0 * forward_flops_per_token(m)


def attention_flops(m: dict, batch: int, seq: int, steps: float) -> float:
    """The least causal attention has to do in `steps` training steps, in
    the attention layers (scores and values over `head_dim`, half under
    the mask, three times with the backward pass)."""
    per_layer = 3 * 0.5 * 2.0 * batch * m["n_head"] * seq * seq * 2 * m["head_dim"]
    return steps * _kinds(m).count("attn") * per_layer


def state_step_bytes(m: dict, row_steps: float, state_bytes: int = 4) -> float:
    """Bytes the Gated DeltaNet layers' state has to move for `row_steps`
    (live rows summed over decode steps: the `state_rows` the model sows):
    every such layer's S (heads x 96 x 192 at the cell) and conv state read
    once and written once."""
    hv = m["linear_num_value_heads"]
    s = hv * m["linear_key_head_dim"] * m["linear_value_head_dim"]
    conv = (m["linear_conv_kernel_dim"] - 1) * (
        2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
        + hv * m["linear_value_head_dim"])
    return float(2 * row_steps * _kinds(m).count("gdn") * (s + conv) * state_bytes)


def _ffn_params(m: dict) -> int:
    """The dense feed-forward's three matrices, over every layer."""
    return m["n_layer"] * 3 * m["hidden_size"] * m["intermediate_size"]


def ffn_step_bytes(m: dict, steps: float, weight_bytes: int = 2) -> float:
    """Bytes the dense feed-forward has to read in `steps` decode steps:
    every layer's three matrices once a step, whatever the rows."""
    return float(steps * _ffn_params(m) * weight_bytes)


def ffn_step_flops(m: dict, row_steps: float) -> float:
    """Operations the dense feed-forward has to do for `row_steps` (live
    rows summed over decode steps): 2 a weight a row."""
    return float(2.0 * row_steps * _ffn_params(m))


def decode_step_bytes(m: dict, live_context_tokens: int, weight_bytes: int = 2,
                      cache_bytes: int = 2) -> float:
    """The least one decode step has to read and write: every weight but the
    embedding once, one row's state, and the keys and values of the live
    context once in the attention layers."""
    kv = 2 * m["n_kv_head"] * m["head_dim"]
    return float(
        (n_params(m) - m["vocab_size"] * m["hidden_size"]) * weight_bytes
        + state_step_bytes(m, 1)
        + _kinds(m).count("attn") * live_context_tokens * kv * cache_bytes
    )


# -------------------------------------------------- weights from `--seed`
PUBLISHED_HIDDEN = 3840
TOP_KEY = 1_000_000  # a fold-in beside the layers' indices
OUT_PROJECTIONS = ("o", "out", "down")
POST_NORMS = ("attn_norm", "mlp_norm")
POST_NORM_SCALE = 0.1


def _leaf(key, name: str, shape, m: dict):
    """One leaf, bfloat16-rounded values in the model's dtype. Sizes: 0.02
    for matrices (the out-projections 0.02 / sqrt(2 L)), larger by the root
    of the published width over the model's at the tests' width; the
    embedding 1; the two post-norms of a block 0.1 (1 + 0.02 z): what a
    sub-layer adds to the stream is its output normalised and scaled by
    them, a tenth of the stream's size, as Xing4's recipe reasons (in a
    trained model a sub-layer moves the stream by a fraction of it; at 1,
    the stream would be its last sub-layers' outputs); every other norm
    (the QK-norms, the gated norm, the final norm) 1 + 0.02 z; the
    convolution 0.5 (its four taps then keep the variance of its input);
    `A_log` the log of a uniform draw over [1, 16] and `dt_bias` the
    inverse softplus of a log-uniform draw over [0.001, 0.1] (Mamba2's
    ranges, as for Qwen3-Next)."""
    wide = math.sqrt(PUBLISHED_HIDDEN / m["hidden_size"])
    dtype = jnp.dtype(m.get("dtype", "bfloat16"))
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    z = jax.random.normal(key, shape, jnp.float32)
    if name in POST_NORMS:
        val = POST_NORM_SCALE * (1.0 + 0.02 * z)
    elif "norm" in name:
        val = 1.0 + 0.02 * z
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


def layer_params(m: dict, key, index, kind: str) -> dict:
    """Layer `index`'s leaves (call under `jit`; `index` may be traced)."""
    k = jax.random.fold_in(key, index)
    ids = _leaf_ids(m)
    return {
        name: _leaf(jax.random.fold_in(k, ids[name]), name, shape, m)
        for name, shape in sorted(_layer_shapes(m, kind).items())
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
    declares it: each period's layers stacked, the Gated DeltaNet ones
    (periods, 3, ...)."""
    tree = top_params(m, key)
    kinds = _kinds(m)
    n = m["full_attention_interval"]
    periods = m["n_layer"] // n
    gdn = jnp.asarray([i for i, k in enumerate(kinds) if k == "gdn"])
    attn = jnp.asarray([i for i, k in enumerate(kinds) if k == "attn"])
    gdn_stack = lax.map(lambda i: layer_params(m, key, i, "gdn"), gdn)
    gdn_stack = jax.tree_util.tree_map(lambda a: a.reshape((periods, n - 1) + a.shape[1:]), gdn_stack)
    attn_stack = lax.map(lambda i: layer_params(m, key, i, "attn"), attn)
    tree["periods"] = {"gdn": {"layer": gdn_stack}, "attn": attn_stack}
    return tree


def layer_of(params, i: int, m: dict) -> dict:
    """Layer `i`'s leaves out of a whole tree."""
    n = m["full_attention_interval"]
    p, j = divmod(i, n)
    if j == n - 1:
        return jax.tree_util.tree_map(lambda a: a[p], params["periods"]["attn"])
    return jax.tree_util.tree_map(lambda a: a[p, j], params["periods"]["gdn"]["layer"])


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
    """No cell trains this family: say so where a reference of a training
    step is asked for."""
    raise NotImplementedError(
        "the olmo_hybrid family trains in no cell: its reference is the forward pass alone"
    )


# ---------------------------------------------------- the plain reference
# `quant` of the reference: a rounding of `reference.QUANT` for every
# product's operands, or one of two changes to the mathematics that the
# limit of `correct` has to see (the products exact): the write strength
# without its factor 2, or a rotary embedding added to the attention.
MATH_CONTROLS = ("beta_sigmoid", "rope")
ROPE = {"partial_rotary_factor": 1.0, "rope_theta": 10000.0}


def _attention(x, lp, m: dict, q, quant=None):
    t = x.shape[0]
    h, d, eps = m["n_head"], m["head_dim"], m["rms_norm_eps"]
    qh = _Q._rms(_Q._mm(x, lp["q"], q), lp["q_norm"], eps).reshape(t, h, d)
    kh = _Q._rms(_Q._mm(x, lp["k"], q), lp["k_norm"], eps).reshape(t, h, d)
    vh = _Q._mm(x, lp["v"], q).reshape(t, h, d)
    if quant == "rope":
        qh, kh = (_Q._rope(a, {**ROPE, "head_dim": d}) for a in (qh, kh))
    s = jnp.einsum("qhd,khd->hqk", q(qh), q(kh), precision=R.HIGHEST) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    a = jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(s, axis=-1)), q(vh), precision=R.HIGHEST)
    return _Q._mm(a.reshape(t, h * d), lp["o"], q)


def _gated_delta(x, lp, m: dict, q, quant=None):
    """The Gated DeltaNet mixer on (T, C), token by token."""
    t = x.shape[0]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv, width = m["linear_key_head_dim"], m["linear_value_head_dim"], m["linear_conv_kernel_dim"]
    n_conv = 2 * hk * dk + hv * dv
    qkvz = _Q._mm(x, lp["in_qkvz"], q)
    ba = _Q._mm(x, lp["in_ba"], q)
    mixed, z = qkvz[:, :n_conv], qkvz[:, n_conv:].reshape(t, hv, dv)
    padded = jnp.concatenate([jnp.zeros((width - 1, n_conv)), mixed])
    mixed = jax.nn.silu(sum(lp["conv"][j] * padded[j:j + t] for j in range(width)))
    qh = _Q._l2(mixed[:, :hk * dk].reshape(t, hk, dk)) * dk ** -0.5
    kh = _Q._l2(mixed[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
    vh = mixed[:, 2 * hk * dk:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv]) * (1.0 if quant == "beta_sigmoid" else 2.0)
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, hv:] + lp["dt_bias"])

    def one(s, xs):
        qt, kt, vt, gt, bt = xs
        s = s * jnp.exp(gt)[:, None, None]
        delta = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt, precision=R.HIGHEST))
        s = s + kt[:, :, None] * delta[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=R.HIGHEST)

    _, o = lax.scan(one, jnp.zeros((hv, dk, dv)), (qh, kh, vh, g, beta))
    o = _Q._rms(o, lp["out_norm"], m["rms_norm_eps"]) * jax.nn.silu(z)
    return _Q._mm(o.reshape(t, hv * dv), lp["out"], q)


def _ffn(x, lp, q):
    g, u = jnp.split(_Q._mm(x, lp["gate_up"], q), 2, axis=-1)
    return _Q._mm(jax.nn.silu(g) * u, lp["down"], q)


def layer(x, lp, m: dict, kind: str, quant=None):
    """One post-norm layer on the stream x (T, C) of one sequence, float32.
    `quant`: a rounding of `reference.QUANT`, or one of `MATH_CONTROLS`."""
    q = R.QUANT.get(quant, R.QUANT[None])
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    eps = m["rms_norm_eps"]
    mixer = _attention if kind == "attn" else _gated_delta
    h = x + _Q._rms(mixer(x, lp, m, q, quant), lp["attn_norm"], eps)
    return h + _Q._rms(_ffn(h, lp, q), lp["mlp_norm"], eps)


def head(top, h, m: dict, quant=None):
    """Logits of the stream h (T, C)."""
    top = {k: top[k].astype(jnp.float32) for k in ("norm_f", "lm_head")}
    q = R.QUANT.get(quant, R.QUANT[None])
    return _Q._mm(_Q._rms(h, top["norm_f"], m["rms_norm_eps"]), top["lm_head"], q)


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


def judge_tokens(gap) -> float:
    """The widest gap of the served tokens. A dense model has no router
    whose choice a rounding can flip, so no token is judged apart (the
    routed families' robust and fragile split)."""
    gap = np.asarray(gap)
    return float(gap.max()) if gap.size else 0.0


def token_gaps(m: dict, seed: int, samples, *, quants=(None,)) -> dict:
    """Per reference in `quants` (None, the plain one, first), per sample of
    (prompt ids, served ids), teacher forced, an array a served token: by
    how far the reference's best logit lies above its logit of the served
    token (None) or of the token that reference puts first (the others).
    One layer's weights are made from the seed, used on every sample's
    stream at once and dropped. Every sample is padded at its end to the
    longest's length (a causal model's earlier positions do not see it) and
    its served positions to the most served, so that each function
    compiles once."""
    t0 = time.monotonic()
    # The loop has dropped its engine, which its own jitted methods refer
    # back to: only the cycle collector frees it, and this reference needs
    # the room.
    gc.collect()
    held = jax.live_arrays()
    log(f"reference starts with {sum(a.nbytes for a in held) / 1e9:.2f} GB in {len(held)} live arrays")
    key = R.seed_key(seed)
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
    streams = {qn: start(top, jnp.asarray(padded)) for qn in quants}
    make = jax.jit(lambda k, i, kind: layer_params(m, k, i, kind), static_argnums=2)
    step = jax.jit(
        lambda x, lp, kind, qn: jax.vmap(lambda s: layer(s, lp, m, kind, qn))(x),
        static_argnums=(2, 3), donate_argnums=0,
    )
    for i, kind in enumerate(_kinds(m)):
        lp = make(key, i, kind)
        for qn in quants:
            streams[qn] = step(streams[qn], lp, kind, qn)
        del lp
    jax.block_until_ready(list(streams.values()))
    t_layers = time.monotonic() - t0

    @functools.partial(jax.jit, static_argnums=3)
    def logits_at(top, x, at, qn):
        return head(top, x[at], m, qn)

    out = {qn: [] for qn in quants}
    for j, ((lo, hi), seq) in enumerate(zip(spans, seqs)):
        n = max(hi - lo, 0)
        at = jnp.asarray(np.minimum(lo + np.arange(rows), length - 1))
        logits = logits_at(top, streams[None][j], at, None)[:n]
        best = jnp.max(logits, axis=-1)
        for qn in quants:
            if qn is None:
                pick = jnp.asarray(seq[lo + 1:lo + 1 + n])
            else:
                pick = jnp.argmax(logits_at(top, streams[qn][j], at, qn)[:n], axis=-1)
            gap = best - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
            out[qn].append(np.asarray(gap))
    log(f"reference: {len(seqs)} sequences padded to {length}, {rows} served positions each, "
        f"{len(quants)} reference(s); layers {t_layers:.1f}s, "
        f"head {time.monotonic() - t0 - t_layers:.1f}s")
    return out


def serve_gaps(m: dict, seed: int, samples, *, quant=None) -> dict:
    """`judge_tokens` of the served tokens of `samples` (`token_gaps`): the
    widest gap by which a served token's logit lies below the reference's
    best; with `quant`, beside it the same of the token the lower precision
    puts first (the control), and log lines of the same for each of
    `MATH_CONTROLS` (the limit has to see them too)."""
    controls = () if quant is None else (quant,) + tuple(
        c for c in MATH_CONTROLS if c != quant
    )
    gaps = token_gaps(m, seed, samples, quants=(None,) + controls)
    joined = {qn: np.concatenate(g) if g else np.zeros(0) for qn, g in gaps.items()}
    gap = joined[None]
    if gap.size:
        log(f"reference: {gap.size} served tokens; share of gaps over 0 / 0.01 / 0.05: "
            f"{float(np.mean(gap > 0)):.4f} / {float(np.mean(gap > 0.01)):.4f} / "
            f"{float(np.mean(gap > 0.05)):.4f}")
    for qn in controls[1:]:
        low = joined[qn]
        log(f"reference with {qn}: judged {judge_tokens(low):.4f}, share over 0: "
            f"{float(np.mean(low > 0)) if low.size else 0.0:.4f}")
    low = joined[quant] if quant is not None else gap
    return {"widest_gap": judge_tokens(gap), "widest_gap_low": judge_tokens(low),
            "tokens": int(gap.size)}
