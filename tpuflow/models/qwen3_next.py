"""Qwen3-Next (Qwen, ``model_type: qwen3_next``): Gated DeltaNet layers and
gated full attention in a period of four, every layer routed over 512
experts with a shared expert.

Token ids (B, T) int32 → logits (B, T, vocab) float32, with the call contract
``tpuflow.infer.serve.ServeEngine`` uses on the other models (``decode``,
``prefill``, ``pad_lens``, ``slot_index``, ``page_table``; a ``cache``
collection; ``config.n_ctx / kv_pages / kv_page_size``; ``clone(config=...)``)
and one more: **state held per slot**. ``slot_state`` names the cache leaves
that are a fixed-size state a request carries from token to token, not a
page per position; the engine's clone declares ``state_slots`` rows of them
(``max_slots`` + a trash row, the last), and a paged call names the row of
each of its rows in ``state_slots`` (B,).

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

**The layer stack.** A loop over the ``n_period`` periods; in each, a loop
over its three Gated DeltaNet layers, then its attention layer. Weights are
declared stacked by kind: the Gated DeltaNet layers' under
``periods/gdn/layer`` at ``(n_period, 3, ...)``, the attention layers' under
``periods/attn`` at ``(n_period, ...)``, every layer's routed experts in two
leaves of their own. Both loops close over the whole stacks, and a layer
reads its weights out of them by index (``[period, j]``, ``[period]``,
``layer``) inside the loop that runs it, where the chip fuses the read into
the product. A period's slice of a stack is never a loop's operand (a
scanned input, as flax's ``variable_axes`` makes it): the compiler makes
such an operand a buffer of its own, and each decode step would copy every
period's 151 MB of ``in_qkvz``, reading and writing it before the products
read it once more.

Precision: weights at rest and in the products in ``dtype`` (bfloat16) with
float32 accumulation; the router, the residual stream, the norms'
statistics, softmax, logits, g, β, the state S and the conv state in
float32; the key/value cache in ``dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import ClassVar

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tpuflow.models.sdar import (
    grouped_attention, paged_attention, rotate_half, route, row_attention,
)
from tpuflow.models.xing4 import HIGHEST, dot, gated_ffn, rms_norm, routed_experts
from tpuflow.ops import gated_delta, paged_pool


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
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
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    n_experts: int = 512  # the router's outputs
    n_experts_per_tok: int = 10
    experts_held: int = 512  # experts [experts_first, + experts_held) live here
    experts_first: int = 0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    gdn_chunk: int = gated_delta.CHUNK
    dtype: jnp.dtype = jnp.bfloat16
    # The serving engine's page pool and state rows: set by its clone.
    kv_pages: int = 0
    kv_page_size: int = 0
    state_slots: int = 0

    @property
    def kv_width(self) -> int:
        """What a token holds in each of the cache's two leaves."""
        return self.n_kv_head * self.head_dim

    @property
    def block_length(self) -> int:
        """The attention mask's block (``models/sdar.py``): 1, causal."""
        return 1

    @property
    def n_period(self) -> int:
        return self.n_layer // self.full_attention_interval

    @property
    def n_gdn(self) -> int:
        return self.n_period * (self.full_attention_interval - 1)

    @property
    def conv_dim(self) -> int:
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def state_shapes(self, rows: int) -> dict[str, tuple[int, ...]]:
        """The per-slot state leaves for ``rows`` rows: the conv's last
        inputs and the delta rule's S, every Gated DeltaNet layer's."""
        # The conv's last inputs lie as one vector a row: with the taps as
        # an axis of their own (3, under the chip's 8-row tile) the chip
        # moves the slot axis inside them, and every program that indexes
        # a slot copies the leaf (tests/test_paged_pool_inplace.py).
        return {
            "conv": (self.n_gdn, rows,
                     (self.linear_conv_kernel_dim - 1) * self.conv_dim),
            "state": (self.n_gdn, rows, self.linear_num_value_heads,
                      self.linear_key_head_dim, self.linear_value_head_dim),
        }


def centred_norm(x, w, eps: float):
    """Qwen3-Next's RMSNorm: ``x / rms(x) · (1 + w)``, in ``x``'s dtype."""
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def l2_normalize(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def rotate_partial(x, positions, cfg: Qwen3NextConfig):
    """Rotary over the first ``rotary_dim`` of the head, halves of them
    rotated; the rest passed through. ``x`` (B, T, H, D)."""
    r = cfg.rotary_dim
    return jnp.concatenate(
        [rotate_half(x[..., :r], positions, cfg.rope_theta), x[..., r:]], axis=-1
    )


def _state_rows(leaf, layer, slots):
    """A layer's state for the call's rows: by slot row from the engine's
    leaf (layers, slots, ...), or the call's own rows (layers, B, ...)."""
    if slots is None:
        return lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    return leaf[layer, slots]


def _put_state_rows(leaf, new, layer, slots):
    """Write them back in place, by index."""
    new = new.astype(leaf.dtype)
    if slots is None:
        return lax.dynamic_update_index_in_dim(leaf, new, layer, 0)
    return leaf.at[layer, slots].set(new)


class _Block(nn.Module):
    """What both kinds of layer share: the routed feed-forward with its
    shared expert, and their parameters' declaration. Every leaf is
    declared with ``stack`` in front, the layers of this kind stacked
    (``(n_period,)`` or ``(n_period, 3)``), and a call reads its own layer
    out of it by index ``at``, inside the loop that runs the layer."""

    config: Qwen3NextConfig
    stack: tuple[int, ...]

    def _p(self, name, shape, at, init=None, dtype=None):
        init = init or nn.initializers.normal(0.02)
        leaf = self.param(name, init, self.stack + tuple(shape),
                          dtype or self.config.dtype)
        for i in at:
            leaf = lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
        return leaf

    def _norm(self, name, x, at):
        w = self._p(name, (x.shape[-1],), at, nn.initializers.zeros)
        return centred_norm(x, w, self.config.rms_norm_eps)

    def _moe(self, x, at, valid, experts, layer):
        cfg = self.config
        b, t, c = x.shape
        flat = x.reshape(b * t, c)
        with jax.named_scope("router"):
            idx, w = route(flat, self._p("router", (c, cfg.n_experts), at), cfg)
        with jax.named_scope("moe_experts"):
            y, sizes = routed_experts(
                flat, idx, w, valid.reshape(-1), *experts, cfg.dtype, layer,
                lo=cfg.experts_first,
            )
        with jax.named_scope("moe_shared"):
            f = cfg.shared_expert_intermediate_size
            shared = gated_ffn(
                flat, self._p("shared_gate_up", (c, 2 * f), at),
                self._p("shared_down", (f, c), at), cfg.dtype,
            )
            gate = jax.nn.sigmoid(jnp.einsum(
                "nc,co->no", flat.astype(jnp.float32),
                self._p("shared_gate", (c, 1), at).astype(jnp.float32),
                precision=HIGHEST,
            ))
            y = y + gate * shared
        return y.reshape(b, t, c), sizes


class GatedDeltaLayer(_Block):
    """A Gated DeltaNet layer and its feed-forward on the stream ``x`` (B,
    T, C) float32. ``cache`` is (keys, values, conv, state), threaded
    through; ``at`` (period, j) indexes this layer in its stacked leaves,
    ``gdn`` in the last two of the cache, ``layer`` in ``experts``.
    ``slots`` (B,): each row's state row in the engine's leaves, None where
    the leaves are the call's own rows."""

    @nn.compact
    def __call__(self, x, cache, at, gdn, layer, valid, experts, slots):
        h, cache = self._mixer(
            self._norm("attn_norm", x, at), cache, at, gdn, valid, slots
        )
        h = x + h
        y, sizes = self._moe(self._norm("mlp_norm", h, at), at, valid, experts, layer)
        return h + y, cache, sizes

    def _mixer(self, x, cache, at, gdn, valid, slots):
        cfg = self.config
        dt = cfg.dtype
        b, t, c = x.shape
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        n_conv, width = cfg.conv_dim, cfg.linear_conv_kernel_dim
        keys, values, conv_leaf, state_leaf = cache
        with jax.named_scope("gdn_in"):
            qkvz = dot(x, self._p("in_qkvz", (c, n_conv + hv * dv), at), dt)
            ba = dot(x, self._p("in_ba", (c, 2 * hv), at), dt, jnp.float32)
            mixed, z = qkvz[..., :n_conv], qkvz[..., n_conv:]
        with jax.named_scope("gdn_conv"):
            mixed = jnp.where(valid[..., None], mixed.astype(jnp.float32), 0.0)
            prev = _state_rows(conv_leaf, gdn, slots).reshape(b, width - 1, n_conv)
            window = jnp.concatenate([prev, mixed], axis=1)  # (B, width - 1 + T, channels)
            w = self._p("conv", (width, n_conv), at).astype(jnp.float32)
            out = sum(w[j] * window[:, j:j + t] for j in range(width))
            conv_leaf = _put_state_rows(
                conv_leaf, window[:, t:].reshape(b, -1), gdn, slots
            )
            mixed = jax.nn.silu(out)
        with jax.named_scope("gdn_core"):
            q = l2_normalize(mixed[..., :hk * dk].reshape(b, t, hk, dk)) * dk ** -0.5
            k = l2_normalize(mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk))
            v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)
            q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
            a_log = self._p("A_log", (hv,), at, nn.initializers.zeros, jnp.float32)
            dt_bias = self._p("dt_bias", (hv,), at, nn.initializers.ones, jnp.float32)
            beta = jnp.where(valid[..., None], jax.nn.sigmoid(ba[..., :hv]), 0.0)
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                ba[..., hv:] + dt_bias.astype(jnp.float32)
            )
            g = jnp.where(valid[..., None], g, 0.0)
            s = _state_rows(state_leaf, gdn, slots)
            if t == 1:
                o, s = gated_delta.step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s)
                o = o[:, None]
            else:
                o, s = gated_delta.chunked(q, k, v, g, beta, s, cfg.gdn_chunk)
            state_leaf = _put_state_rows(state_leaf, s, gdn, slots)
        with jax.named_scope("gdn_out"):
            o = rms_norm(o, self._p("out_norm", (dv,), at, nn.initializers.ones),
                         cfg.rms_norm_eps)
            o = o * jax.nn.silu(z.reshape(b, t, hv, dv).astype(jnp.float32))
            out_init = nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer))
            y = dot(o.reshape(b, t, hv * dv), self._p("out", (hv * dv, c), at, out_init),
                    dt, jnp.float32)
        return y, (keys, values, conv_leaf, state_leaf)


class GatedAttentionLayer(_Block):
    """Gated full attention and its feed-forward; ``attn`` (the period)
    indexes this layer in its stacked leaves and in the key/value cache.
    The cache's paths are SDAR's."""

    @nn.compact
    def __call__(self, x, cache, attn, layer, positions, valid, experts, *,
                 decode, pad_lens, slot_index, page_table, start):
        at = (attn,)
        h, cache = self._attention(
            self._norm("attn_norm", x, at), cache, attn, positions, decode,
            pad_lens, slot_index, page_table, start,
        )
        h = x + h
        y, sizes = self._moe(self._norm("mlp_norm", h, at), at, valid, experts, layer)
        return h + y, cache, sizes

    def _attention(self, x, cache, attn, positions, decode, pad_lens,
                   slot_index, page_table, start):
        cfg = self.config
        dt, eps = cfg.dtype, cfg.rms_norm_eps
        b, t, c = x.shape
        h, g, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        kv, rest = cache[:2], cache[2:]
        at = (attn,)
        with jax.named_scope("attn_q"):
            qg = dot(x, self._p("q", (c, h * 2 * d), at), dt).reshape(b, t, h, 2 * d)
            gate = qg[..., d:]
            q = rotate_partial(
                centred_norm(qg[..., :d], self._p("q_norm", (d,), at, nn.initializers.zeros), eps),
                positions, cfg,
            )
        with jax.named_scope("kv_write"):
            k = dot(x, self._p("k", (c, g * d), at), dt).reshape(b, t, g, d)
            k = rotate_partial(
                centred_norm(k, self._p("k_norm", (d,), at, nn.initializers.zeros), eps),
                positions, cfg,
            ).reshape(b, t, g * d)
            v = dot(x, self._p("v", (c, g * d), at), dt)
        if not decode:
            a = grouped_attention(q, k, v, positions, positions, pad_lens, cfg)
        elif page_table is not None:
            a, kv = paged_attention(
                q, k, v, kv, attn, positions, pad_lens, slot_index, page_table, cfg
            )
        else:
            a, kv = row_attention(q, k, v, kv, attn, positions, pad_lens, start, cfg)
        with jax.named_scope("attn_gate"):
            a = (a.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dt)
        with jax.named_scope("attn_out"):
            out_init = nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer))
            y = dot(a.reshape(b, t, h * d), self._p("o", (h * d, c), at, out_init),
                    dt, jnp.float32)
        return y, kv + rest


class _GdnScan(nn.Module):
    """Scan-body adapter over a period's Gated DeltaNet layers: carry (x,
    cache), scanned input ``j``, the layer's place in the period."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, carry, j, period, valid, experts, slots):
        cfg = self.config
        n = cfg.full_attention_interval - 1
        x, cache = carry
        x, cache, sizes = GatedDeltaLayer(cfg, (cfg.n_period, n), name="layer")(
            x, cache, (period, j), period * n + j, period * (n + 1) + j,
            valid, experts, slots,
        )
        return (x, cache), sizes


def _scan(module, ctx: nn.Module, **kw):
    """``nn.scan`` of a body whose parameters the loop closes over whole
    (the body declares them stacked and indexes its own layer). Flax's
    check that they are loop invariants traces the body a second time: it
    runs where the parameters are made, not where they are read."""
    return nn.scan(
        module, variable_broadcast="params", split_rngs={"params": False},
        check_constancy_invariants=ctx.is_initializing(), **kw,
    )


class _Period(nn.Module):
    """One period: ``full_attention_interval - 1`` Gated DeltaNet layers (a
    loop of their own, which closes over their whole stacked leaves and
    reads each layer's at ``[period, j]``: the period's slice as the loop's
    operand would be copied every step, module docstring) and one gated
    attention layer. Scan-body adapter: carry (x, cache), scanned input the
    period's index."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, carry, period, positions, valid, experts, decode,
                 pad_lens, slot_index, page_table, start, slots):
        cfg = self.config
        n = cfg.full_attention_interval - 1
        scan = _scan(_GdnScan, self, length=n, in_axes=(0,) + (nn.broadcast,) * 4)
        (x, cache), gdn_sizes = scan(cfg, name="gdn")(
            carry, jnp.arange(n), period, valid, experts, slots,
        )
        x, cache, sizes = GatedAttentionLayer(cfg, (cfg.n_period,), name="attn")(
            x, cache, period, period * (n + 1) + n, positions, valid, experts,
            decode=decode, pad_lens=pad_lens, slot_index=slot_index,
            page_table=page_table, start=start,
        )
        return (x, cache), jnp.concatenate([gdn_sizes, sizes[None]])


class Qwen3Next(nn.Module):
    config: Qwen3NextConfig = Qwen3NextConfig()
    # Cache leaves that a request carries as one fixed-size state (asked by
    # the serving engine, ``ServeEngine._init_cache``): every other leaf is
    # a page pool, or an index.
    slot_state: ClassVar[tuple[str, ...]] = ("conv", "state")

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, decode: bool = False,
                 pad_lens=None, prefill: bool = False, slot_index=None,
                 page_table=None, state_slots=None):
        """See the module docstring. ``train`` changes nothing (no dropout;
        one precision on every path). ``prefill`` computes the head for the
        last position alone (an admission reads no other)."""
        cfg = self.config
        del train
        b, t = tokens.shape
        c = cfg.hidden_size
        as_i32 = lambda a: None if a is None else jnp.asarray(a, jnp.int32)  # noqa: E731
        pad_lens, slot_index, page_table, state_slots = map(
            as_i32, (pad_lens, slot_index, page_table, state_slots)
        )
        paged = decode and slot_index is not None
        if paged and (page_table is None or cfg.kv_pages <= 0 or cfg.state_slots <= 0):
            raise ValueError(
                "slot_index needs a page_table and a config that declares "
                "the pool and the state rows (kv_pages / kv_page_size / "
                "state_slots): the serving engine clones its decode model "
                "with them"
            )
        embed = self.param(
            "embed", nn.initializers.normal(1.0), (cfg.vocab_size, c), cfg.dtype
        )
        start = jnp.int32(0)
        offset = jnp.arange(t)[None, :]
        pads = jnp.zeros((b, 1), jnp.int32) if pad_lens is None else pad_lens[:, None]
        valid = offset >= pads
        slots = None
        if paged:
            leaf = (cfg.n_period, cfg.kv_pages, cfg.kv_page_size,
                    paged_pool.token_width(cfg.kv_width))
            store = [
                self.variable("cache", name, jnp.zeros, leaf, cfg.dtype)
                for name in ("k", "v")
            ]
            store += [
                self.variable("cache", name, jnp.zeros, shape, jnp.float32)
                for name, shape in cfg.state_shapes(cfg.state_slots).items()
            ]
            # Kept beside them so that a pool and a prefill row have one
            # structure (the engine's insert maps the two together).
            self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            positions = slot_index[:, None] + offset - pads
            # A row whose table is all trash is dead (the engine zeroes it).
            valid = jnp.broadcast_to(page_table[:, :1] != 0, (b, t))
            trash = cfg.state_slots - 1
            slots = jnp.full((b,), trash, jnp.int32) if state_slots is None else state_slots
            # Rows whose state this call moves on (the engine's dead rows
            # write the trash row).
            self.sow("step_sum", "state_rows", jnp.sum(slots != trash),
                     reduce_fn=lambda _, v: v, init_fn=lambda: 0)
        elif decode:
            leaf = (cfg.n_period, b, cfg.n_ctx, cfg.kv_width)
            store = [
                self.variable("cache", name, jnp.zeros, leaf, cfg.dtype)
                for name in ("k", "v")
            ]
            store += [
                self.variable("cache", name, jnp.zeros, shape, jnp.float32)
                for name, shape in cfg.state_shapes(b).items()
            ]
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
            cache = tuple(var.value for var in store)
        else:  # a plain forward: no keys kept, every state from nought
            cache = (jnp.zeros((), cfg.dtype),) * 2 + tuple(
                jnp.zeros(shape, jnp.float32) for shape in cfg.state_shapes(b).values()
            )
        positions = jnp.clip(positions, 0, None)

        x = embed[tokens].astype(jnp.float32)
        f = cfg.moe_intermediate_size
        experts = (
            self.param("experts_gate_up", nn.initializers.normal(0.02),
                       (cfg.n_layer, cfg.experts_held, c, 2 * f), cfg.dtype),
            self.param(
                "experts_down",
                nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer)),
                (cfg.n_layer, cfg.experts_held, f, c), cfg.dtype,
            ),
        )
        scan = _scan(
            _Period, self, length=cfg.n_period, in_axes=(0,) + (nn.broadcast,) * 9
        )
        (x, cache), sizes = scan(cfg, name="periods")(
            (x, cache), jnp.arange(cfg.n_period), positions, valid, experts,
            decode, pad_lens, slot_index, page_table, start, slots,
        )
        if decode:
            for var, new in zip(store, cache):
                var.value = new
        # What a serving engine may read of a forward pass (it sums
        # `step_sum` and takes the largest of `step_max` over a call's).
        live = jnp.maximum(jnp.sum(valid), 1) * cfg.n_experts_per_tok
        self.sow(
            "step_sum", "experts_touched", jnp.sum(sizes > 0),
            reduce_fn=lambda _, v: v, init_fn=lambda: 0,
        )
        self.sow(
            "step_max", "expert_max_load",
            jnp.max(sizes) * cfg.n_experts / live,
            reduce_fn=lambda _, v: v, init_fn=lambda: 0,
        )
        norm_f = self.param("norm_f", nn.initializers.zeros, (c,), cfg.dtype)
        lm_head = self.param(
            "lm_head", nn.initializers.normal(0.02), (c, cfg.vocab_size), cfg.dtype
        )
        if prefill:
            x = x[:, -1:]
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "btc,cv->btv",
                centred_norm(x, norm_f, cfg.rms_norm_eps).astype(cfg.dtype),
                lm_head.astype(cfg.dtype), preferred_element_type=jnp.float32,
            )
