"""Olmo-Hybrid (allenai, ``model_type: olmo_hybrid``): Gated DeltaNet layers
with negative eigenvalues and full multi-head attention in a period of
four, Olmo 2/3's post-norm block around each, a dense SwiGLU in every
layer.

Token ids (B, T) int32 → logits (B, T, vocab) float32, through the hybrid
stack of ``models/hybrid.py`` (its call contract, its state held per slot,
its layer loop); this file holds the model's numbers and choices.

The equations, x a layer's input (what the published config does not say
is marked *assumed*; ``benchmark/configs`` lists the same). RMSNorm is the
plain ``x / rms(x) · w``, eps ``rms_norm_eps`` (1e-6), statistics in
float32. The block (Olmo 2/3's, *assumed* for both mixers): ``h = x +
RMSNorm_a(mixer(x))``, then ``y = h + RMSNorm_f(ffn(h))``; neither
sub-layer normalises its input. A final RMSNorm, then an untied head.

**Gated DeltaNet** (layers i with (i + 1) mod 4 ≠ 0; ``layer_types``).
``W_qkvz`` maps 3,840 to q (30 key heads × 96), k (30 × 96), v (30 value
heads × 192) and z (30 × 192), in that order of columns (*assumed*); ``W_ba``
to b (30) and a (30). ``[q; k; v]`` (11,520 channels) goes through a causal
depthwise convolution of width 4, no bias, then SiLU. q and k are
L2-normalised per head, q scaled by 96^-1/2; value head h reads key head h,
with no repeat. ``β = 2 · sigmoid(b)`` (``linear_allow_neg_eigval``: fla's
``beta * 2``), ``g = −exp(A_log) · softplus(a + dt_bias)``, per head,
float32. Per head and token, with S (96 × 192, float32): ``S ← e^g S; δ = β
(v − Sᵀk); S ← S + k δᵀ; o = Sᵀq``. The transition ``I − β k kᵀ`` has
eigenvalues down to −1. ``o`` goes through a gated RMSNorm per head,
``rms(o) · w ⊙ silu(z)``, then ``W_out`` (5,760 → 3,840).

**Full attention** (every fourth layer). q, k, v and o are 3,840 × 3,840,
no bias. q and k each get one RMSNorm over all 3,840 channels before they
are split into 30 heads of 128 (Olmo 2/3's QK-norm). No rotary embedding
(``rope_parameters.rope_theta`` is null in the published config: read as
NoPE, *assumed*). Causal softmax in float32, scale 128^-1/2, no gate, then
``W_o``. The cache holds keys after norm, and values: 3,840 numbers a token
a leaf, 30 whole 128-lane rows.

**Feed-forward**, every layer: ``W_down (silu(W_gate h) ⊙ W_up h)``, width
``intermediate_size`` (11,008), under the scope ``ffn``.

Precision: weights at rest and in the products in ``dtype`` (bfloat16) with
float32 accumulation; the residual stream, the norms' statistics, softmax,
logits, g, β, the state S and the conv state in float32; the key/value
cache in ``dtype``.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from tpuflow.models.hybrid import HybridConfig, HybridLM


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig(HybridConfig):
    vocab_size: int = 100352
    n_ctx: int = 65536  # positions served (the cache's length)
    hidden_size: int = 3840
    n_layer: int = 32
    full_attention_interval: int = 4
    n_head: int = 30
    n_kv_head: int = 30
    head_dim: int = 128
    partial_rotary_factor: float = 0.0
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    block_norm: str = "post"
    norm_offset: float = 0.0
    ffn: str = "dense"
    intermediate_size: int = 11008
    attn_gate: bool = False
    qk_norm: str = "proj"
    gdn_beta_scale: float = 2.0
    rms_norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16


class OlmoHybrid(HybridLM):
    config: OlmoHybridConfig = OlmoHybridConfig()
