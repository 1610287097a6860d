"""Xing4.0 (XingChen-AGI, ``model_type: xing4_0``): latent attention, routed
experts with a shared expert, four residual streams under manifold-constrained
hyper-connections, and a multi-token-prediction module.

Token ids (B, T) int32 → logits (B, T, vocab) float32, with the call contract
``tpuflow.infer.serve.ServeEngine`` uses on GPT-2 (``decode``, ``prefill``,
``pad_lens``, ``slot_index``, ``page_table``; a ``cache`` collection;
``config.n_ctx / kv_pages / kv_page_size``; ``clone(config=...)``).

The equations, x a token's input to the sub-layer (what the published
config does not say is marked *assumed*; ``benchmark/configs`` lists the same):

Latent attention (DeepSeek-V2/V3 form, which the config's keys are).
``c_q = RMSNorm(x W_qa)``; ``[q_nope; q_rope]_h = c_q W_qb`` (128 + 64 a
head), ``q_rope`` rotated. ``[c_kv; k_rope] = x W_kva`` (512 + 64); ``c_kv =
RMSNorm(c_kv)``; ``k_rope`` rotated, one for all heads. ``[k_nope; v]_h = c_kv
W_kvb`` (128 + 128 a head). ``score = (q_nope·k_nope + q_rope·k_rope) ·
192^-1/2 · m²``, ``m = 0.1 · mscale_all_dim · ln(factor) + 1``; causal
softmax in float32; heads joined through ``W_o``. No biases. Rotary on the 64
``rope`` dimensions, consecutive pairs rotated (*assumed*: DeepSeek's
interleaved convention), YaRN frequencies (``yarn_inv_freq``); the factor on
cos and sin is ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
mscale_all_dim)`` = 1. **The cache holds ``[c_kv after its norm; k_rope after
rotation]``**, ``kv_lora_rank + qk_rope_head_dim`` numbers a token a layer
(576; the engine's page pool keeps them in 640 lanes, ``_paged``).
Two attention paths, one result: ``_expanded`` makes ``k_nope, v`` from the
chunk's latents and attends with full heads (a fresh prefill, a plain
forward); ``_absorbed`` folds ``W_kvb``'s key half into the query (128 → 512
a head) and its value half in after the weighted sum, and attends over the
latents as they lie in the cache (every decode step, a warm-cache chunk).

Feed-forward. The first ``first_k_dense`` layers dense: ``(silu(x W_g) ⊙ x
W_u) W_d``. The rest routed: ``s = sigmoid(x W_r)`` in float32; the
``n_experts_per_tok`` experts with the largest ``s + b``
(``e_score_correction_bias``, ``noaux_tc``; one group, so the group step is
the identity); weights ``s_e / Σ_chosen s`` times ``routed_scaling_factor``;
``y = Σ_e w_e · FFN_e(x) + FFN_shared(x)``. No token is dropped, whatever the
load: the (token, expert) pairs are sorted by expert and go through two
grouped products (``ops/grouped_matmul.py``: on a TPU the Pallas grouped
matrix product that ships with jax, elsewhere ``lax.ragged_dot``), which visit the
experts some live token chose and no other. Every routed layer's experts lie
in one leaf (layers, experts, ...), which the products take whole, the
layer's groups alone non-empty: a layer's slice of it is never copied.
Tokens the caller marks dead (pad columns of a prefill, rows of a decode
block whose page table is all trash) are routed nowhere.

Residual path (``hc_mult`` streams; arXiv:2512.24880). The stream is ``X`` of
shape (hc_mult, hidden) a token; the embedding is copied into the rows and
the rows are summed before the final norm (*assumed*, the hyper-connections
paper's form). Around every sub-layer F, with its own parameters: ``x̃ =
RMSNorm(vec(X))`` (no scale, *assumed*); ``H̃_pre = α_pre · x̃ φ_pre + b_pre``,
``H̃_post = α_post · x̃ φ_post + b_post``, ``H̃_res = α_res · mat(x̃ φ_res) +
b_res``; ``H_pre = σ(H̃_pre)``, ``H_post = 2σ(H̃_post)``, ``H_res =
Sinkhorn(exp(clip(H̃_res)))`` with ``hc_sinkhorn_iters`` alternations, rows
first then columns, each divisor ``+ hc_eps`` (*assumed*: order and where the
eps enters); then ``X ← H_res X + H_postᵀ F(norm(H_pre X))``. Coefficients
and the stream in float32.

Multi-token prediction (``n_mtp`` 1, DeepSeek-V3 form): ``h' = W_m
[RMSNorm(h_i); RMSNorm(Emb(t_{i+1}))]``, one more layer of the expert kind,
the shared final norm and head: the logits of ``t_{i+2}``. It takes and
returns the summed stream (*assumed*). Only on a plain forward (``mtp=True``);
a served configuration sets ``n_mtp = 0`` and does not hold it.

Precision: weights at rest and in the products in ``dtype`` (bfloat16) with
float32 accumulation; the router, the hyper-connection coefficients, the
residual stream, softmax and logits in float32; the cache in ``dtype``.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tpuflow.ops import paged_pool
from tpuflow.ops.attention import attention
from tpuflow.ops.grouped_matmul import grouped_dot

HIGHEST = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    vocab_size: int = 131072
    n_ctx: int = 4096  # positions served (the cache's length)
    hidden_size: int = 3584
    n_layer: int = 40  # dense + expert layers
    first_k_dense: int = 2
    n_head: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    n_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple[float, float] = (-30.0, 30.0)
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    n_mtp: int = 0
    attn_impl: str = "auto"
    dtype: jnp.dtype = jnp.bfloat16
    # The serving engine's page pool (see GPT2Config): set by its clone.
    kv_pages: int = 0
    kv_page_size: int = 0

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_moe_layer(self) -> int:
        return self.n_layer - self.first_k_dense

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


# ------------------------------------------------------------------ rotary
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's frequencies: the source's below the dimension ``beta_fast``
    rotations pick out, the source's over ``factor`` above the one
    ``beta_slow`` picks, a linear ramp between."""
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / theta ** exponent
    inter = extra / factor

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original_max / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
    )
    return inter * ramp + extra * (1.0 - ramp)


def rotate(x, positions, cfg: Xing4Config):
    """Rotate consecutive pairs of the last axis by their position's
    angles. ``x`` (B, T, ..., rope_dim); ``positions`` (B, T)."""
    inv = yarn_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
        cfg.rope_original_max, cfg.rope_beta_fast, cfg.rope_beta_slow,
    )
    scale = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / yarn_mscale(
        cfg.rope_factor, cfg.rope_mscale_all_dim
    )
    ang = positions.astype(jnp.float32)[..., None] * inv  # (B, T, dim/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    pair = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pair[..., 0], pair[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ------------------------------------------------------------ small pieces
def rms_norm(x, scale, eps: float):
    """RMSNorm in float32, returned in ``x``'s dtype; ``scale`` None = 1."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    return y.astype(x.dtype)


def dot(x, w, dtype, out=None):
    """``x @ w`` over the last axis of x, operands in ``dtype``, float32
    accumulation, result in ``out`` (``dtype`` unless given)."""
    return jnp.einsum(
        "...c,cd->...d", x.astype(dtype), w.astype(dtype),
        preferred_element_type=jnp.float32,
    ).astype(out or dtype)


def gated_ffn(x, w_gate_up, w_down, dtype):
    """Float32 out: what a sub-layer adds to the stream is not rounded."""
    g, u = jnp.split(dot(x, w_gate_up, dtype), 2, axis=-1)
    return dot(nn.silu(g) * u, w_down, dtype, jnp.float32)


def sinkhorn(m, iters: int, eps: float):
    """``iters`` alternations of row then column normalisation of the
    positive (..., n, n) matrices ``m``."""

    def body(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    return lax.fori_loop(0, iters, body, m, unroll=True)


def hc_coefficients(X, phi, alpha, b, cfg: Xing4Config):
    """(H_pre (…, n), H_post (…, n), H_res (…, n, n)) from the streams
    ``X`` (…, n, C), float32."""
    n = cfg.hc_mult
    flat = X.reshape(X.shape[:-2] + (-1,)).astype(jnp.float32)
    xt = rms_norm(flat, None, cfg.rms_norm_eps)
    raw = jnp.einsum(
        "...c,cd->...d", xt, phi.astype(jnp.float32), precision=HIGHEST
    )
    alpha, b = alpha.astype(jnp.float32), b.astype(jnp.float32)
    pre = alpha[0] * raw[..., :n] + b[:n]
    post = alpha[1] * raw[..., n:2 * n] + b[n:2 * n]
    res = alpha[2] * raw[..., 2 * n:] + b[2 * n:]
    res = jnp.exp(jnp.clip(res, *cfg.hc_clamp)).reshape(res.shape[:-1] + (n, n))
    return (
        jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
        sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps),
    )


def route(x, w_router, bias, cfg: Xing4Config):
    """The router: (chosen experts (N, k) int32, their weights (N, k)
    float32) for tokens ``x`` (N, C). Float32 at the highest precision: a
    flipped choice moves a logit further than a product's rounding."""
    s = jax.nn.sigmoid(jnp.einsum(
        "nc,ce->ne", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=HIGHEST,
    ))
    _, idx = lax.top_k(s + bias.astype(jnp.float32), cfg.n_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


def routed_experts(x, idx, w, valid, w_gate_up, w_down, dtype, layer=0):
    """``Σ_e w_e · FFN_e(x)`` over the chosen experts of tokens ``x`` (N, C)
    with ``valid`` (N,) marking the live ones: the pairs sorted by expert
    through two grouped products. ``w_gate_up`` (L, E, C, 2F) and
    ``w_down`` (L, E, F, C) hold every routed layer's experts, ``layer``
    says which of them are this call's. Returns (y (N, C) float32, tokens
    each expert got (E,) int32)."""
    n, k = idx.shape
    n_layers, n_experts = w_gate_up.shape[:2]
    expert = jnp.where(valid[:, None], idx, n_experts).reshape(-1)
    order = jnp.argsort(expert, stable=True)  # dead pairs sort last
    sizes = jnp.bincount(expert, length=n_experts + 1)[:n_experts].astype(
        jnp.int32
    )
    # The other layers' experts are groups of no rows.
    groups = lax.dynamic_update_slice(
        jnp.zeros((n_layers * n_experts,), jnp.int32), sizes,
        (layer * n_experts,),
    )
    flat = lambda a: a.reshape((-1,) + a.shape[2:]).astype(dtype)  # noqa: E731
    xs = x.astype(dtype)[order // k]
    h = grouped_dot(xs, flat(w_gate_up), groups, groups=n_experts).astype(dtype)
    g, u = jnp.split(h, 2, axis=-1)
    y = grouped_dot(nn.silu(g) * u, flat(w_down), groups, groups=n_experts)
    # Rows past the last group hold nothing the products define.
    live = (jnp.arange(n * k) < jnp.sum(sizes))[:, None]
    y = jnp.where(live, y, 0.0)[jnp.argsort(order)].reshape(n, k, -1)
    return jnp.einsum("nkc,nk->nc", y, jnp.where(valid[:, None], w, 0.0)), sizes


# ------------------------------------------------------------------ a layer
class Layer(nn.Module):
    """One layer on the streams ``X`` (B, T, hc_mult, C): latent attention
    and a feed-forward (dense, or routed with a shared expert), each inside
    its hyper-connection. ``cache`` is the whole latent cache, threaded
    through: a pool (layers, kv_pages, page_size, latent padded to whole
    128-lane rows) read and written through ``page_table``, or rows
    (layers, B, n_ctx, latent) written at ``start``; ``layer`` is this
    layer's index into it. ``experts`` is
    (gate_up, down) of every routed layer, which the caller holds."""

    config: Xing4Config
    moe: bool

    def _p(self, name, shape, init=None):
        init = init or nn.initializers.normal(0.02)
        return self.param(name, init, shape, self.config.dtype)

    def _hc(self, X, name, sub):
        """``X ← H_res X + H_postᵀ sub(norm(H_pre X))``."""
        cfg = self.config
        n, c = cfg.hc_mult, cfg.hidden_size
        with jax.named_scope("mhc"):
            pre, post, res = hc_coefficients(
                X,
                self._p(f"hc_{name}_phi", (n * c, 2 * n + n * n)),
                self._p(f"hc_{name}_alpha", (3,), nn.initializers.ones),
                self._p(f"hc_{name}_b", (2 * n + n * n,), nn.initializers.zeros),
                cfg,
            )
            # Float32 into the sub-layer: its products round it to their
            # own precision, the router does not (a choice flipped by the
            # rounding of its input moves a logit further than any product).
            x = rms_norm(
                jnp.einsum("btn,btnc->btc", pre, X),
                self._p(f"{name}_norm", (c,), nn.initializers.ones),
                cfg.rms_norm_eps,
            )
        y = sub(x)
        with jax.named_scope("mhc"):
            return jnp.einsum("btnm,btmc->btnc", res, X) + (
                post[..., None] * y.astype(jnp.float32)[:, :, None, :]
            )

    @nn.compact
    def __call__(self, X, cache, layer, positions, valid, experts=None, *,
                 decode: bool, pad_lens=None, slot_index=None, page_table=None,
                 start=None):
        sizes = None

        def attn(x):
            nonlocal cache
            y, cache = self._attention(
                x, cache, layer, positions, decode, pad_lens, slot_index,
                page_table, start,
            )
            return y

        def ffn(x):
            nonlocal sizes
            if not self.moe:
                return self._dense_ffn(x)
            y, sizes = self._moe_ffn(
                x, valid, experts, layer - self.config.first_k_dense
            )
            return y

        X = self._hc(X, "attn", attn)
        X = self._hc(X, "mlp", ffn)
        return X, cache, sizes

    # -------------------------------------------------------- feed-forward
    def _dense_ffn(self, x):
        cfg = self.config
        c, f = cfg.hidden_size, cfg.intermediate_size
        out_init = nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer))
        with jax.named_scope("mlp"):
            return gated_ffn(
                x, self._p("mlp_gate_up", (c, 2 * f)),
                self._p("mlp_down", (f, c), out_init), cfg.dtype,
            )

    def _moe_ffn(self, x, valid, experts, moe_layer):
        cfg = self.config
        c, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
        out_init = nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer))
        b, t, _ = x.shape
        flat = x.reshape(b * t, c)
        with jax.named_scope("router"):
            idx, w = route(
                flat, self._p("router", (c, e)),
                self._p("router_bias", (e,), nn.initializers.zeros), cfg,
            )
        with jax.named_scope("moe_experts"):
            y, sizes = routed_experts(
                flat, idx, w, valid.reshape(-1), *experts, cfg.dtype, moe_layer
            )
        with jax.named_scope("moe_shared"):
            fs = f * cfg.n_shared_experts
            y = y + gated_ffn(
                flat, self._p("shared_gate_up", (c, 2 * fs)),
                self._p("shared_down", (fs, c), out_init), cfg.dtype,
            )
        return y.reshape(b, t, c), sizes

    # ----------------------------------------------------------- attention
    def _attention(self, x, cache, layer, positions, decode, pad_lens,
                   slot_index, page_table, start):
        cfg = self.config
        dt = cfg.dtype
        b, t, c = x.shape
        h, r = cfg.n_head, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        out_init = nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer))
        ones = nn.initializers.ones
        with jax.named_scope("mla_q"):
            cq = rms_norm(
                dot(x, self._p("q_a", (c, cfg.q_lora_rank)), dt),
                self._p("q_norm", (cfg.q_lora_rank,), ones), cfg.rms_norm_eps,
            )
            q = dot(cq, self._p("q_b", (cfg.q_lora_rank, h * (dn + dr))), dt)
            q = q.reshape(b, t, h, dn + dr)
            q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], positions, cfg)
        with jax.named_scope("kv_write"):
            kv = dot(x, self._p("kv_a", (c, r + dr)), dt)
            latent = jnp.concatenate([
                rms_norm(kv[..., :r], self._p("kv_norm", (r,), ones),
                         cfg.rms_norm_eps),
                rotate(kv[..., r:], positions, cfg),
            ], axis=-1)
        w_kvb = self._p("kv_b", (r, h * (dn + dv))).reshape(r, h, dn + dv)
        if not decode:
            valid = None
            if pad_lens is not None:
                k_pos = jnp.arange(t)
                valid = (k_pos[None, None, :] <= k_pos[None, :, None]) & (
                    k_pos[None, None, :] >= pad_lens[:, None, None]
                )
            a = self._expanded(q_nope, q_rope, latent, w_kvb, valid)
        elif page_table is not None:
            a, cache = self._paged(
                q_nope, q_rope, latent, w_kvb, cache, layer, pad_lens,
                slot_index, page_table,
            )
        else:
            a, cache = self._rows(
                q_nope, q_rope, latent, w_kvb, cache, layer, pad_lens, start
            )
        with jax.named_scope("mla_out"):
            return dot(
                a.reshape(b, t, h * dv), self._p("o", (h * dv, c), out_init), dt,
                jnp.float32,
            ), cache

    def _expanded(self, q_nope, q_rope, latent, w_kvb, valid):
        """Full heads: ``k_nope, v`` made from the chunk's own latents.
        ``valid`` (B, Tq, Tk), or None for a plain causal forward, which
        goes through the attention dispatch."""
        cfg = self.config
        dt, r, dn = cfg.dtype, cfg.kv_lora_rank, cfg.qk_nope_head_dim
        b, t, h, _ = q_nope.shape
        with jax.named_scope("kv_expand"):
            kv = jnp.einsum(
                "btr,rhd->bthd", latent[..., :r], w_kvb.astype(dt),
                preferred_element_type=jnp.float32,
            ).astype(dt)
            k_rope = jnp.broadcast_to(
                latent[:, :, None, r:], (b, t, h, cfg.qk_rope_head_dim)
            )
            k = jnp.concatenate([kv[..., :dn], k_rope], axis=-1)
            v = kv[..., dn:]
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if valid is None:
            # `attention` scales by the query's own width, 192^-1/2: the
            # YaRN factor m² rides on the query.
            m2 = cfg.softmax_scale * math.sqrt(q.shape[-1])
            return attention(
                (q.astype(jnp.float32) * m2).astype(dt), k, v, causal=True,
                impl=cfg.attn_impl,
            )
        with jax.named_scope("attn_core"):
            s = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
            ) * cfg.softmax_scale
            s = jnp.where(valid[:, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(dt)
            return jnp.einsum(
                "bhqk,bkhd->bqhd", p, v, preferred_element_type=jnp.float32
            ).astype(dt)

    def _absorbed(self, q_nope, q_rope, latents, w_kvb, valid):
        """Attention in the latent space: ``latents`` (B, N, latent) as they
        lie in the cache, ``valid`` (B, Tq, N)."""
        cfg = self.config
        dt, r, dn = cfg.dtype, cfg.kv_lora_rank, cfg.qk_nope_head_dim
        with jax.named_scope("attn_core"):
            q_abs = jnp.einsum(
                "bthd,rhd->bthr", q_nope, w_kvb[..., :dn].astype(dt),
                preferred_element_type=jnp.float32,
            ).astype(dt)
            q = jnp.concatenate([q_abs, q_rope], axis=-1)  # (B, T, H, latent)
            s = jnp.einsum(
                "bthl,bnl->bhtn", q, latents.astype(dt),
                preferred_element_type=jnp.float32,
            ) * cfg.softmax_scale
            s = jnp.where(valid[:, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum(
                "bhtn,bnr->bthr", p, latents[..., :r].astype(dt),
                preferred_element_type=jnp.float32,
            ).astype(dt)
            return jnp.einsum(
                "bthr,rhd->bthd", o, w_kvb[..., dn:].astype(dt),
                preferred_element_type=jnp.float32,
            ).astype(dt)

    def _paged(self, q_nope, q_rope, latent, w_kvb, pool, layer, pad_lens,
               slot_index, page_table):
        """The serving engine's page pool (GPT-2's ``_paged_attention``, one
        latent leaf in place of K and V): row b's T new latents land at
        logical columns ``slot_index[b] + t`` through its table, columns
        beyond the table and dead rows in the layer's trash page 0; each
        row reads the pages its table names and nothing else. The pool is
        (layers, kv_pages, page_size, 640): the latent's 576 numbers and
        64 zero lanes, because the chip lays a leaf out page-major, as it
        is indexed here, only when its minor axis fills whole 128-lane
        rows (``ops/paged_pool.py``); the lanes are sliced off the
        gathered rows, so nothing they hold reaches a product."""
        cfg = self.config
        t = q_nope.shape[1]
        ps, d = cfg.kv_page_size, cfg.latent_dim
        width = page_table.shape[1] * ps
        first_page = layer * cfg.kv_pages
        pos, flat = paged_pool.token_slots(
            page_table, slot_index, t, ps, first_page
        )
        with jax.named_scope("kv_write"):
            pool = paged_pool.write_tokens(pool, flat, latent)
        with jax.named_scope("kv_read"):
            latents = paged_pool.read_rows(pool, first_page + page_table, (d,))
        k_pos = jnp.arange(width)
        valid = k_pos[None, None, :] <= pos[:, :, None]
        if pad_lens is not None:
            valid = valid & (k_pos[None, None, :] >= pad_lens[:, None, None])
        return self._absorbed(q_nope, q_rope, latents, w_kvb, valid), pool

    def _rows(self, q_nope, q_rope, latent, w_kvb, rows, layer, pad_lens,
              start):
        """Contiguous rows (layers, B, n_ctx, latent): the chunk's latents
        written at ``start``; a fresh multi-token chunk attends with full
        heads over itself, anything else over the rows in the latent
        space."""
        cfg = self.config
        t = q_nope.shape[1]
        with jax.named_scope("kv_write"):
            rows = lax.dynamic_update_slice(
                rows, latent.astype(rows.dtype)[None], (layer, 0, start, 0)
            )
        q_pos = start + jnp.arange(t)
        pads = (
            jnp.zeros((q_nope.shape[0],), jnp.int32) if pad_lens is None
            else pad_lens
        )

        def over_rows():
            with jax.named_scope("kv_read"):
                mine = lax.dynamic_index_in_dim(rows, layer, 0, keepdims=False)
            k_pos = jnp.arange(cfg.n_ctx)
            valid = (k_pos[None, None, :] <= q_pos[None, :, None]) & (
                k_pos[None, None, :] >= pads[:, None, None]
            )
            return self._absorbed(q_nope, q_rope, mine, w_kvb, valid)

        def fresh():
            k_pos = jnp.arange(t)
            valid = (k_pos[None, None, :] <= k_pos[None, :, None]) & (
                k_pos[None, None, :] >= pads[:, None, None]
            )
            return self._expanded(q_nope, q_rope, latent, w_kvb, valid)

        if t > 1:
            return lax.cond(start == 0, fresh, over_rows), rows
        return over_rows(), rows


class _ScanLayer(nn.Module):
    """Scan-body adapter: carry (X, cache), scanned input the layer's index,
    scanned output the tokens each expert got."""

    config: Xing4Config

    @nn.compact
    def __call__(self, carry, layer, positions, valid, experts, decode,
                 pad_lens, slot_index, page_table, start):
        X, cache = carry
        X, cache, sizes = Layer(self.config, moe=True, name="layer")(
            X, cache, layer, positions, valid, experts, decode=decode,
            pad_lens=pad_lens, slot_index=slot_index, page_table=page_table,
            start=start,
        )
        return (X, cache), sizes


class Xing4(nn.Module):
    config: Xing4Config = Xing4Config()

    def _experts(self, name: str, layers: int):
        """(gate_up, down) of ``layers`` routed layers' experts, one leaf
        each, whole: the grouped products index into it."""
        cfg = self.config
        c, f, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
        return (
            self.param(f"{name}_gate_up", nn.initializers.normal(0.02),
                       (layers, e, c, 2 * f), cfg.dtype),
            self.param(
                f"{name}_down",
                nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer)),
                (layers, e, f, c), cfg.dtype,
            ),
        )

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, decode: bool = False,
                 pad_lens=None, prefill: bool = False, slot_index=None,
                 page_table=None, mtp: bool = False):
        """See the module docstring. ``train`` and ``prefill`` change
        nothing here (no dropout; one precision on every path). With
        ``mtp=True`` (a plain forward of a model built with ``n_mtp`` 1)
        returns (logits, the multi-token module's logits)."""
        cfg = self.config
        del train, prefill
        b, t = tokens.shape
        c, n = cfg.hidden_size, cfg.hc_mult
        as_i32 = lambda a: None if a is None else jnp.asarray(a, jnp.int32)  # noqa: E731
        pad_lens, slot_index, page_table = map(
            as_i32, (pad_lens, slot_index, page_table)
        )
        paged = decode and slot_index is not None
        if paged and (page_table is None or cfg.kv_pages <= 0):
            raise ValueError(
                "slot_index needs a page_table and a config that declares "
                "the pool (kv_pages / kv_page_size): the serving engine "
                "clones its decode model with them"
            )
        embed = self.param(
            "embed", nn.initializers.normal(1.0), (cfg.vocab_size, c), cfg.dtype
        )
        # The cache, its start, each token's position and which tokens live.
        cache, start = jnp.zeros((), cfg.dtype), jnp.int32(0)
        offset = jnp.arange(t)[None, :]
        pads = jnp.zeros((b, 1), jnp.int32) if pad_lens is None else pad_lens[:, None]
        valid = offset >= pads
        if paged:
            var = self.variable(
                "cache", "latent", jnp.zeros,
                (cfg.n_layer, cfg.kv_pages, cfg.kv_page_size,
                 paged_pool.token_width(cfg.latent_dim)),
                cfg.dtype,
            )
            # Kept beside it so that a pool and a prefill row have one
            # structure (the engine's insert maps the two together).
            self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            positions = slot_index[:, None] + offset - pads
            # A row whose table is all trash is dead (the engine zeroes it).
            valid = jnp.broadcast_to(page_table[:, :1] != 0, (b, t))
        elif decode:
            var = self.variable(
                "cache", "latent", jnp.zeros,
                (cfg.n_layer, b, cfg.n_ctx, cfg.latent_dim), cfg.dtype,
            )
            index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            start = index.value
            index.value = start + t
            positions = start + offset - pads
            valid = start + offset >= pads
        else:
            positions = offset - pads
        if decode:
            cache = var.value
        positions = jnp.clip(positions, 0, None)

        X = jnp.broadcast_to(
            embed[tokens].astype(jnp.float32)[:, :, None, :], (b, t, n, c)
        )
        call = dict(
            decode=decode, pad_lens=pad_lens, slot_index=slot_index,
            page_table=page_table, start=start,
        )
        for i in range(cfg.first_k_dense):
            X, cache, _ = Layer(cfg, moe=False, name=f"dense_{i}")(
                X, cache, i, positions, valid, **call
            )
        sizes = None
        if cfg.n_moe_layer:
            experts = self._experts("experts", cfg.n_moe_layer)
            scan = nn.scan(
                _ScanLayer, variable_axes={"params": 0},
                split_rngs={"params": True}, length=cfg.n_moe_layer,
                in_axes=(0,) + (nn.broadcast,) * 8,
            )
            (X, cache), sizes = scan(cfg, name="layers")(
                (X, cache), cfg.first_k_dense + jnp.arange(cfg.n_moe_layer),
                positions, valid, experts, decode, pad_lens, slot_index,
                page_table, start,
            )
        if decode:
            var.value = cache
        if sizes is not None:
            # What a serving engine may read of a step (it sums `step_sum`
            # and takes the largest of `step_max` over a block's steps).
            live = jnp.maximum(jnp.sum(valid), 1) * cfg.n_experts_per_tok
            self.sow(
                "step_sum", "experts_touched", jnp.sum(sizes > 0),
                reduce_fn=lambda _, v: v, init_fn=lambda: 0,
            )
            self.sow(
                "step_max", "expert_max_load",
                jnp.max(sizes) * cfg.n_routed_experts / live,
                reduce_fn=lambda _, v: v, init_fn=lambda: 0,
            )
        h = jnp.sum(X, axis=2)  # the streams summed: (B, T, C) float32
        norm_f = self.param("norm_f", nn.initializers.ones, (c,), cfg.dtype)
        lm_head = self.param(
            "lm_head", nn.initializers.normal(0.02), (c, cfg.vocab_size), cfg.dtype
        )

        def head(h):
            with jax.named_scope("lm_head"):
                return jnp.einsum(
                    "btc,cv->btv",
                    rms_norm(h, norm_f, cfg.rms_norm_eps).astype(cfg.dtype),
                    lm_head.astype(cfg.dtype), preferred_element_type=jnp.float32,
                )

        logits = head(h)
        if not mtp:
            return logits
        if cfg.n_mtp != 1 or decode:
            raise ValueError("mtp=True needs n_mtp == 1 and a plain forward")
        with jax.named_scope("mtp"):
            ones = nn.initializers.ones
            nxt = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], 1)
            joined = jnp.concatenate([
                rms_norm(h, self.param("mtp_hnorm", ones, (c,), cfg.dtype),
                         cfg.rms_norm_eps),
                rms_norm(embed[nxt].astype(jnp.float32),
                         self.param("mtp_enorm", ones, (c,), cfg.dtype),
                         cfg.rms_norm_eps),
            ], axis=-1).astype(cfg.dtype)
            proj = self.param(
                "mtp_proj", nn.initializers.normal(0.02), (2 * c, c), cfg.dtype
            )
            X = jnp.broadcast_to(
                dot(joined, proj, cfg.dtype).astype(jnp.float32)[:, :, None, :],
                (b, t, n, c),
            )
            X, _, _ = Layer(cfg, moe=True, name="mtp_layer")(
                X, cache, cfg.first_k_dense, positions, valid,
                self._experts("mtp_experts", 1), **call
            )
            return logits, head(jnp.sum(X, axis=2))
