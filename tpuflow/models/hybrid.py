"""The hybrid stack: Gated DeltaNet layers and full attention in periods,
every layer with a feed-forward, a fixed-size state per slot beside the
paged key/value pool. One code path for every model of this shape
(``models/qwen3_next.py``, ``models/olmo_hybrid.py``: their docstrings
write out the equations); what differs between them is a field of
``HybridConfig``:

- ``block_norm``: ``pre``, ``h = x + mixer(norm(x))`` and ``y = h +
  ffn(norm(h))``; or ``post``, ``h = x + norm(mixer(x))`` and ``y = h +
  norm(ffn(h))``, no sub-layer normalising its input.
- ``norm_offset``: 1, zero-centred RMSNorm ``x / rms(x) · (1 + w)``; 0, the
  plain ``x / rms(x) · w``. Every norm of the block, of attention's query
  and key and the final norm (not the gated norm of a Gated DeltaNet
  output, a plain scale in both).
- ``ffn``: ``moe``, softmax top-k routed experts held in a range of the
  router's, with a shared expert under a sigmoid gate; or ``dense``, a
  SwiGLU of ``intermediate_size``.
- ``attn_gate``: the query projection gives each head a gate beside its
  query, the output times ``sigmoid(gate)``; or no gate.
- ``qk_norm``: ``head``, RMSNorm over each head's query and key; or
  ``proj``, one RMSNorm over the whole query and the whole key projection
  before they are split into heads.
- ``partial_rotary_factor``: rotary over that share of each head, halves
  of it rotated; 0 is no rotary embedding at all.
- ``gdn_beta_scale``: ``β = scale · sigmoid(b)``; 2 lets the transition
  ``I − β k kᵀ`` reach eigenvalues down to −1.
- ``linear_num_key_heads`` under ``linear_num_value_heads``: value head h
  reads key head ``⌊h · hk / hv⌋``; where they are equal, each its own.

**The layer stack.** A loop over the ``n_period`` periods; in each, a loop
over its Gated DeltaNet layers, then its attention layer. Weights are
declared stacked by kind: the Gated DeltaNet layers' under
``periods/gdn/layer`` at ``(n_period, interval - 1, ...)``, the attention
layers' under ``periods/attn`` at ``(n_period, ...)``, a routed model's
experts in two leaves of their own. Both loops close over the whole
stacks, and a layer reads its weights out of them by index (``[period,
j]``, ``[period]``, ``layer``) inside the loop that runs it, where the
chip fuses the read into the product. A period's slice of a stack is never
a loop's operand (a scanned input, as flax's ``variable_axes`` makes it):
the compiler makes such an operand a buffer of its own, and each decode
step would copy every period's weights (151 MB of Qwen3-Next's
``in_qkvz``), reading and writing them before the products read them once
more.

**The call contract** is ``tpuflow.infer.serve.ServeEngine``'s (``decode``,
``prefill``, ``pad_lens``, ``slot_index``, ``page_table``; a ``cache``
collection; ``config.n_ctx / kv_pages / kv_page_size``; ``clone(config=...)``)
and one more: **state held per slot**. ``slot_state`` names the cache leaves
that are a fixed-size state a request carries from token to token, not a
page per position; the engine's clone declares ``state_slots`` rows of them
(``max_slots`` + a trash row, the last), and a paged call names the row of
each of its rows in ``state_slots`` (B,).

**Gated DeltaNet.** ``W_qkvz`` maps C to q (hk × dk), k (hk × dk), v (hv ×
dv) and z (hv × dv), in that order of columns; ``W_ba`` to b (hv) and a
(hv). ``[q; k; v]`` goes through a causal depthwise convolution of width
``linear_conv_kernel_dim``, no bias, then SiLU; a row carries its last
inputs (float32, one vector) from call to call. q and k are L2-normalised
per head (``x · rsqrt(Σx² + 1e-6)``), q scaled by dk^-1/2. ``g = −exp(A_log)
· softplus(a + dt_bias)``, per value head, float32. Per head and token,
with S (dk × dv, float32): ``S ← e^g S; δ = β (v − Sᵀk); S ← S + k δᵀ; o
= Sᵀq`` (``ops/gated_delta.py``: the chunked form over a prompt, one step a
token in decode). A serving engine's decode call advances each row's S
and conv state where they lie in the engine's leaves, by slot, in one
kernel each on the TPU (``gated_delta.step_in_place``,
``conv_step_in_place``); off it, and for a paged call of several tokens,
it gathers the rows' states by slot and scatters them back.
``o`` goes through a gated RMSNorm per head, ``rms(o) · w ⊙ silu(z)``,
then ``W_out``. At a left pad the conv's input is nought, β =
0 and g = 0: the state stays what it was, zero before a prompt's first
token.

**Full attention** over SDAR's paths (``models/sdar.py``, at a block length
of 1): the cache holds keys after norm (and rotation) and values,
``n_kv_head · head_dim`` numbers a token a leaf, whole 128-lane rows
(``ops/paged_pool.py``). Causal softmax in float32, scale head_dim^-1/2.

Precision: weights at rest and in the products in ``dtype`` with float32
accumulation; the router, the residual stream, the norms' statistics,
softmax, logits, g, β, the state S and the conv state in float32; the
key/value cache in ``dtype``.
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
class HybridConfig:
    """The numbers of a hybrid model and its choices (module docstring).
    A model's own config gives each number its published default."""

    vocab_size: int = 0
    n_ctx: int = 0  # positions served (the cache's length)
    hidden_size: int = 0
    n_layer: int = 0
    full_attention_interval: int = 4
    n_head: int = 0
    n_kv_head: int = 0
    head_dim: int = 0
    partial_rotary_factor: float = 0.0
    rope_theta: float = 10000.0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    block_norm: str = "pre"  # or "post"
    norm_offset: float = 1.0  # 1: zero-centred norms; 0: plain
    ffn: str = "moe"  # or "dense"
    intermediate_size: int = 0  # the dense feed-forward's width
    attn_gate: bool = True
    qk_norm: str = "head"  # or "proj"
    gdn_beta_scale: float = 1.0
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0
    n_experts: int = 0  # the router's outputs
    n_experts_per_tok: int = 0
    experts_held: int = 0  # experts [experts_first, + experts_held) live here
    experts_first: int = 0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    gdn_chunk: int = gated_delta.CHUNK
    dtype: jnp.dtype = jnp.bfloat16
    # The serving engine's page pool and state rows: set by its clone.
    kv_pages: int = 0
    kv_page_size: int = 0
    state_slots: int = 0

    def __post_init__(self):
        for name, value, allowed in (
            ("block_norm", self.block_norm, ("pre", "post")),
            ("ffn", self.ffn, ("moe", "dense")),
            ("qk_norm", self.qk_norm, ("head", "proj")),
        ):
            if value not in allowed:
                raise ValueError(f"{name}={value!r}: one of {allowed}")

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


def norm_scale(w, cfg: HybridConfig):
    """An RMSNorm's scale from its weight: ``1 + w`` (zero-centred) or
    ``w``, per ``norm_offset``."""
    return cfg.norm_offset + w.astype(jnp.float32) if cfg.norm_offset else w


def l2_normalize(x, eps: float = 1e-6):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def rotate_partial(x, positions, cfg: HybridConfig):
    """Rotary over the first ``rotary_dim`` of the head, halves of them
    rotated; the rest passed through (all of it where ``rotary_dim`` is
    0). ``x`` (B, T, H, D)."""
    r = cfg.rotary_dim
    if not r:
        return x
    return jnp.concatenate(
        [rotate_half(x[..., :r], positions, cfg.rope_theta), x[..., r:]], axis=-1
    )


def _state_rows(leaf, layer, slots):
    """A layer's state for the call's rows: by slot row from the engine's
    leaf (layers, slots, ...), or the call's own rows (layers, B, ...).
    On the TPU the states take this path only in a prefill (``slots``
    None): a decode step there advances them in place (``_mixer``)."""
    if slots is None:
        return lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    return leaf[layer, slots]


def _put_state_rows(leaf, new, layer, slots):
    """Write them back in place, by index (where ``_state_rows`` read
    them)."""
    new = new.astype(leaf.dtype)
    if slots is None:
        return lax.dynamic_update_index_in_dim(leaf, new, layer, 0)
    return leaf.at[layer, slots].set(new)


class _Block(nn.Module):
    """What both kinds of layer share: the block around the mixer, the
    feed-forward, and their parameters' declaration. Every leaf is
    declared with ``stack`` in front, the layers of this kind stacked
    (``(n_period,)`` or ``(n_period, interval - 1)``), and a call reads its
    own layer out of it by index ``at``, inside the loop that runs the
    layer."""

    config: HybridConfig
    stack: tuple[int, ...]

    def _p(self, name, shape, at, init=None, dtype=None):
        init = init or nn.initializers.normal(0.02)
        leaf = self.param(name, init, self.stack + tuple(shape),
                          dtype or self.config.dtype)
        for i in at:
            leaf = lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)
        return leaf

    def _norm(self, name, x, at):
        cfg = self.config
        init = nn.initializers.zeros if cfg.norm_offset else nn.initializers.ones
        w = self._p(name, (x.shape[-1],), at, init)
        return rms_norm(x, norm_scale(w, cfg), cfg.rms_norm_eps)

    def _block(self, x, at, mixer, valid, experts, layer):
        """The mixer and the feed-forward on the stream ``x``, pre- or
        post-norm; ``mixer(x)`` returns (what it adds, the cache)."""
        if self.config.block_norm == "pre":
            h, cache = mixer(self._norm("attn_norm", x, at))
            h = x + h
            y, sizes = self._ffn(self._norm("mlp_norm", h, at), at, valid, experts, layer)
            return h + y, cache, sizes
        h, cache = mixer(x)
        h = x + self._norm("attn_norm", h, at)
        y, sizes = self._ffn(h, at, valid, experts, layer)
        return h + self._norm("mlp_norm", y, at), cache, sizes

    def _ffn(self, x, at, valid, experts, layer):
        """(what the feed-forward adds, float32; the tokens each routed
        expert held here took, None for a dense one)."""
        cfg = self.config
        b, t, c = x.shape
        flat = x.reshape(b * t, c)
        if cfg.ffn == "dense":
            f = cfg.intermediate_size
            with jax.named_scope("ffn"):
                y = gated_ffn(flat, self._p("gate_up", (c, 2 * f), at),
                              self._p("down", (f, c), at), cfg.dtype)
            return y.reshape(b, t, c), None
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
        return self._block(
            x, at, lambda x: self._mixer(x, cache, at, gdn, valid, slots),
            valid, experts, layer,
        )

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
        # A decode call on the TPU advances both states where they lie.
        in_place = t == 1 and slots is not None and gated_delta.in_place()
        with jax.named_scope("gdn_conv"):
            mixed = jnp.where(valid[..., None], mixed.astype(jnp.float32), 0.0)
            w = self._p("conv", (width, n_conv), at).astype(jnp.float32)
            if in_place:
                out, conv_leaf = gated_delta.conv_step_in_place(
                    conv_leaf, gdn, slots, mixed[:, 0], w
                )
                out = out[:, None]
            else:
                prev = _state_rows(conv_leaf, gdn, slots).reshape(b, width - 1, n_conv)
                window = jnp.concatenate([prev, mixed], axis=1)  # (B, width - 1 + T, channels)
                out = sum(w[j] * window[:, j:j + t] for j in range(width))
                conv_leaf = _put_state_rows(
                    conv_leaf, window[:, t:].reshape(b, -1), gdn, slots
                )
            mixed = jax.nn.silu(out)
        with jax.named_scope("gdn_core"):
            q = l2_normalize(mixed[..., :hk * dk].reshape(b, t, hk, dk)) * dk ** -0.5
            k = l2_normalize(mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk))
            v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)
            if hv != hk:
                q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
            a_log = self._p("A_log", (hv,), at, nn.initializers.zeros, jnp.float32)
            dt_bias = self._p("dt_bias", (hv,), at, nn.initializers.ones, jnp.float32)
            live = valid[..., None]
            beta = jax.nn.sigmoid(ba[..., :hv])
            if cfg.gdn_beta_scale != 1.0:
                beta = cfg.gdn_beta_scale * beta
            beta = jnp.where(live, beta, 0.0)
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                ba[..., hv:] + dt_bias.astype(jnp.float32)
            )
            g = jnp.where(valid[..., None], g, 0.0)
            if in_place:
                o, state_leaf = gated_delta.step_in_place(
                    state_leaf, gdn, slots, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
                )
                o = o[:, None]
            else:
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


class AttentionLayer(_Block):
    """Full attention (gated or not) and its feed-forward; ``attn`` (the
    period) indexes this layer in its stacked leaves and in the key/value
    cache. The cache's paths are SDAR's."""

    @nn.compact
    def __call__(self, x, cache, attn, layer, positions, valid, experts, *,
                 decode, pad_lens, slot_index, page_table, start):
        def mixer(x):
            return self._attention(x, cache, attn, positions, decode, pad_lens,
                                   slot_index, page_table, start)

        return self._block(x, (attn,), mixer, valid, experts, layer)

    def _qk_norm(self, name, x, at):
        """RMSNorm of each head (``qk_norm`` head) or of the whole
        projection (proj) of ``x`` (B, T, H, D)."""
        cfg = self.config
        if cfg.qk_norm == "head":
            return self._norm(name, x, at)
        b, t, h, d = x.shape
        return self._norm(name, x.reshape(b, t, h * d), at).reshape(b, t, h, d)

    def _attention(self, x, cache, attn, positions, decode, pad_lens,
                   slot_index, page_table, start):
        cfg = self.config
        dt = cfg.dtype
        b, t, c = x.shape
        h, g, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        kv, rest = cache[:2], cache[2:]
        at = (attn,)
        with jax.named_scope("attn_q"):
            if cfg.attn_gate:
                qg = dot(x, self._p("q", (c, h * 2 * d), at), dt).reshape(b, t, h, 2 * d)
                gate, q = qg[..., d:], qg[..., :d]
            else:
                q = dot(x, self._p("q", (c, h * d), at), dt).reshape(b, t, h, d)
            q = rotate_partial(self._qk_norm("q_norm", q, at), positions, cfg)
        with jax.named_scope("kv_write"):
            k = dot(x, self._p("k", (c, g * d), at), dt).reshape(b, t, g, d)
            k = rotate_partial(
                self._qk_norm("k_norm", k, at), positions, cfg
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
        if cfg.attn_gate:
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

    config: HybridConfig

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
    operand would be copied every step, module docstring) and one
    attention layer. Scan-body adapter: carry (x, cache), scanned input the
    period's index."""

    config: HybridConfig

    @nn.compact
    def __call__(self, carry, period, positions, valid, experts, decode,
                 pad_lens, slot_index, page_table, start, slots):
        cfg = self.config
        n = cfg.full_attention_interval - 1
        scan = _scan(_GdnScan, self, length=n, in_axes=(0,) + (nn.broadcast,) * 4)
        (x, cache), gdn_sizes = scan(cfg, name="gdn")(
            carry, jnp.arange(n), period, valid, experts, slots,
        )
        x, cache, sizes = AttentionLayer(cfg, (cfg.n_period,), name="attn")(
            x, cache, period, period * (n + 1) + n, positions, valid, experts,
            decode=decode, pad_lens=pad_lens, slot_index=slot_index,
            page_table=page_table, start=start,
        )
        if sizes is None:
            return (x, cache), None
        return (x, cache), jnp.concatenate([gdn_sizes, sizes[None]])


class HybridLM(nn.Module):
    """Token ids (B, T) int32 → logits (B, T, vocab) float32 (module
    docstring)."""

    config: HybridConfig
    # Cache leaves that a request carries as one fixed-size state (asked by
    # the serving engine, ``ServeEngine._init_cache``): every other leaf is
    # a page pool, or an index.
    slot_state: ClassVar[tuple[str, ...]] = ("conv", "state")

    @staticmethod
    def state_step() -> str:
        """How a decode call advances S and the conv state (the serving
        engine records it): ``inplace``, the kernels on the engine's
        leaves; ``xla``, the rows gathered, stepped and scattered back."""
        return "inplace" if gated_delta.in_place() else "xla"

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, decode: bool = False,
                 pad_lens=None, prefill: bool = False, slot_index=None,
                 page_table=None, state_slots=None):
        """``train`` changes nothing (no dropout; one precision on every
        path). ``prefill`` computes the head for the last position alone
        (an admission reads no other)."""
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
        experts = None
        if cfg.ffn == "moe":
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
        if sizes is not None:
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
        norm_f = self.param(
            "norm_f",
            nn.initializers.zeros if cfg.norm_offset else nn.initializers.ones,
            (c,), cfg.dtype,
        )
        lm_head = self.param(
            "lm_head", nn.initializers.normal(0.02), (c, cfg.vocab_size), cfg.dtype
        )
        if prefill:
            x = x[:, -1:]
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "btc,cv->btv",
                rms_norm(x, norm_scale(norm_f, cfg), cfg.rms_norm_eps).astype(cfg.dtype),
                lm_head.astype(cfg.dtype), preferred_element_type=jnp.float32,
            )
