"""Qwen3-Next (Qwen, ``model_type: qwen3_next``): Gated DeltaNet layers and
gated full attention in a period of four, every layer routed over 512
experts with a shared expert.

Token ids (B, T) int32 → logits (B, T, vocab) float32, through the
hybrid stack of ``models/hybrid.py`` (its call contract, its state held
per slot, its layer loop); this file holds the model's numbers and
choices.

The equations, x a layer's input (what the published config does not say
is marked *assumed*; ``benchmark/configs`` lists the same). Norms are
zero-centred RMSNorm, ``x / rms(x) · (1 + w)``, eps ``rms_norm_eps``,
statistics in float32; ``h = x + mixer(norm(x))``, ``y = h + moe(norm(h))``.

**Gated DeltaNet** (layers i with (i + 1) mod ``full_attention_interval`` ≠
0). ``W_qkvz`` maps C to q (16 key heads × 128), k (16 × 128), v (32 value
heads × 128) and z (32 × 128), in that order of columns (*assumed*: the
column grouping is a layout, and with seeded weights any one is as good);
``W_ba`` to b (32) and a (32). ``[q; k; v]`` (8,192 channels) goes through a
causal depthwise convolution of width ``linear_conv_kernel_dim`` (4), no
bias, then SiLU; a row carries its last three inputs (float32, one vector)
from call to call. q and k are L2-normalised per head (``x · rsqrt(Σx² + 1e-6)``), q
scaled by 128^-1/2, and both repeated to the 32 value heads (head h reads
key head ⌊h / 2⌋). ``β = sigmoid(b)``, ``g = −exp(A_log) · softplus(a +
dt_bias)``, per value head, float32. Per head and token, with S (128 × 128,
float32) the head's state: ``S ← e^g S; δ = β (v − Sᵀk); S ← S + k δᵀ; o =
Sᵀq`` (``ops/gated_delta.py``: the chunked form over a prompt, one step a
token in decode). ``o`` goes through a gated RMSNorm per head, ``rms(o) · w
⊙ silu(z)`` (a plain scale, not zero-centred, as in Qwen3-Next), then
``W_out`` (4,096 → C). At a left pad the conv's input is nought, β = 0 and g
= 0: the state stays what it was, zero before a prompt's first token.

**Gated attention** (every fourth layer). ``W_q`` gives 16 heads of (256
query, 256 gate), in that order within a head; k and v are 2 heads of 256.
RMSNorm on each head's query and key; rotary over the first
``partial_rotary_factor`` (0.25) of the head, 64 of 256 dimensions, θ =
``rope_theta``, halves of those 64 rotated, the other 192 passed through.
Causal softmax in float32 (score scale 256^-1/2), the output times
``sigmoid(gate)``, then ``W_o`` (4,096 → C). The cache holds keys after norm
and rotation and values, 512 numbers a token a leaf: whole 128-lane rows
(``ops/paged_pool.py``). SDAR's grouped attention (``models/sdar.py``, at a
block length of 1) computes it.

**Every layer routed** (``decoder_sparse_step`` 1; ``intermediate_size``
5,120 is used by no layer). ``p = softmax(W_r ĥ)`` over all ``n_experts``
(512) in float32 at the highest precision; the ``n_experts_per_tok`` (10)
largest, renormalised. Experts SiLU-gated of width 512. This chip holds
``experts_held`` of them, ``[experts_first, experts_first + experts_held)``
(expert parallelism): the router keeps its 512 outputs and its top-10, and
a pair whose expert lies elsewhere adds nothing here
(``models/xing4.py`` ``routed_experts``). One shared expert of width 512,
scaled by ``sigmoid(w_sg · ĥ)`` (float32). Final norm, untied head.

**The layer stack** is ``models/hybrid.py``'s, which this model shares
with Olmo-Hybrid: pre-norm blocks with zero-centred norms, a routed
feed-forward, gated attention with per-head QK-norm and partial rotary,
``β = sigmoid(b)``, value heads that repeat key heads. Weights are declared
stacked by kind (``periods/gdn/layer`` at ``(n_period, 3, ...)``,
``periods/attn`` at ``(n_period, ...)``, every layer's routed experts in two
leaves of their own), and a layer reads its own out of the stacks by index
inside the loop that runs it.

Precision: weights at rest and in the products in ``dtype`` (bfloat16) with
float32 accumulation; the router, the residual stream, the norms'
statistics, softmax, logits, g, β, the state S and the conv state in
float32; the key/value cache in ``dtype``.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from tpuflow.models.hybrid import HybridConfig, HybridLM


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig(HybridConfig):
    vocab_size: int = 151936
    n_ctx: int = 4096  # positions served (the cache's length)
    hidden_size: int = 2048
    n_layer: int = 48
    full_attention_interval: int = 4
    n_head: int = 16
    n_kv_head: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    block_norm: str = "pre"
    norm_offset: float = 1.0
    ffn: str = "moe"
    attn_gate: bool = True
    qk_norm: str = "head"
    gdn_beta_scale: float = 1.0
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    n_experts: int = 512  # the router's outputs
    n_experts_per_tok: int = 10
    experts_held: int = 512  # experts [experts_first, + experts_held) live here
    experts_first: int = 0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16


class Qwen3Next(HybridLM):
    config: Qwen3NextConfig = Qwen3NextConfig()
