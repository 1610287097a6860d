"""SDAR (JetLM, ``model_type: sdar_moe``): a Qwen3-MoE-shaped decoder that
generates by diffusion over blocks of ``block_length`` tokens.

Token ids (B, T) int32 → logits (B, T, vocab) float32, with the call contract
``tpuflow.infer.serve.ServeEngine`` uses on GPT-2 and Xing4 (``decode``,
``prefill``, ``pad_lens``, ``slot_index``, ``page_table``; a ``cache``
collection; ``config.n_ctx / kv_pages / kv_page_size``; ``clone(config=...)``)
and one more switch, ``head=False``: a pass that writes keys and values and
computes no logits (the engine's commit pass).

The equations, x a token's input to the layer (what the published config
does not say is marked *assumed*; ``benchmark/configs`` lists the same):

Attention, grouped queries. ``x̂ = RMSNorm(x)``; ``q_h = RoPE(RMSNorm_D(W_q
x̂)_h)`` for ``n_head`` heads, ``k_g = RoPE(RMSNorm_D(W_k x̂)_g)`` and ``v_g =
(W_v x̂)_g`` for ``n_kv_head`` groups of ``n_head / n_kv_head`` heads each,
head size D = ``head_dim``; the two head norms have one scale of D numbers
each, shared by the heads. Scores ``q·k · D^-1/2``, softmax in float32,
heads joined through ``W_o``: ``h = x + W_o Attn``. No biases, no window.
Rotary over the whole head with θ = ``rope_theta``, no scaling, halves
rotated (*assumed*: Qwen3's rotate-half convention, ``[x1; x2] → [x1 cos −
x2 sin; x2 cos + x1 sin]`` with the two halves of the head).

**The mask is block-causal over absolute positions**: position i sees j iff
``⌊j/L⌋ ≤ ⌊i/L⌋``, ``L = block_length`` (*assumed* 4: the config gives none):
every earlier block whole, and its own block in both directions. One rule on
every path: a plain forward, a prefill, and a decode pass over a row's
current block, whose L queries then see every cached position before the
block and all L positions of the block.

Feed-forward, every layer routed (``decoder_sparse_step`` 1, no dense layer,
no shared expert). ``ĥ = RMSNorm(h)``; ``p = softmax(W_r ĥ)`` over all
``n_experts`` in float32; the ``n_experts_per_tok`` largest; ``w_e = p_e /
Σ_chosen p`` (``norm_topk_prob``); ``y = h + Σ_e w_e · W_d^e (silu(W_g^e ĥ) ⊙
W_u^e ĥ)``. No token is dropped: the (token, expert) pairs are sorted by
expert and go through two grouped products (``models/xing4.py``
``routed_experts`` over ``ops/grouped_matmul.py``), which visit the experts
some live token chose and no other. Final RMSNorm, untied head.

**The cache holds a token's keys after their norm and rotation, and its
values**: two leaves, ``n_kv_head · D`` numbers a token a layer each (512 at
the published widths: whole 128-lane rows, so the engine's pool keeps them
with no pad, ``ops/paged_pool.py``). A position's keys and values depend,
from the second layer up, on the other tokens of its block, so they are
final only once the block is: a paged decode pass writes the block's keys
and values at the block's own columns *every* pass and reads them back
through the page table with the pages before them, and whoever drives the
passes (``ServeEngine._denoise_fn``) ends a block with one more pass over the
finished block, whose write is the one later blocks read. What an earlier
pass wrote there no query outside the block's own passes ever sees.

Generation itself (which positions a pass unmasks) is the engine's; a masked
position's input is the embedding of ``mask_id``, which the caller puts in
the ids (the model never asks which positions are masked).

Precision: weights at rest and in the products in ``dtype`` (bfloat16) with
float32 accumulation; the router from the float32 stream at the highest
precision; the residual stream, the norms' statistics, softmax and logits in
float32; the cache in ``dtype``.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from tpuflow.models.xing4 import HIGHEST, dot, rms_norm, routed_experts
from tpuflow.ops import paged_pool


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    vocab_size: int = 151936
    n_ctx: int = 2048  # positions served (the cache's length)
    hidden_size: int = 2048
    n_layer: int = 48
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    n_experts: int = 128
    n_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    # Generation (the config gives neither: *assumed*): the block the mask
    # is built over, and the engine's defaults for the denoise passes a
    # block takes and the id whose embedding a masked position's input is.
    block_length: int = 4
    denoise_steps: int = 2
    mask_id: int = 151669
    dtype: jnp.dtype = jnp.bfloat16
    # The serving engine's page pool (see GPT2Config): set by its clone.
    kv_pages: int = 0
    kv_page_size: int = 0

    @property
    def kv_width(self) -> int:
        """What a token holds in each of the cache's two leaves."""
        return self.n_kv_head * self.head_dim


def rotate_half(x, positions, theta: float):
    """Rotary over the whole last axis, halves rotated. ``x`` (B, T, H, D);
    ``positions`` (B, T)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None, None] * inv  # (B, T, 1, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def route(x, w_router, cfg: SdarConfig):
    """(chosen experts (N, k) int32, their weights (N, k) float32) for
    tokens ``x`` (N, C) float32: softmax over every expert, the largest k,
    renormalised. Float32 at the highest precision: a flipped choice moves
    a logit further than a product's rounding."""
    p = jax.nn.softmax(jnp.einsum(
        "nc,ce->ne", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=HIGHEST,
    ), axis=-1)
    w, idx = lax.top_k(p, cfg.n_experts_per_tok)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), w


def block_causal(q_pos, k_pos, block_length: int):
    """(..., Tq, Tk) bool: key position j is visible to query position i."""
    return (k_pos[..., None, :] // block_length) <= (
        q_pos[..., :, None] // block_length
    )


def grouped_attention(q, keys, values, q_pos, k_pos, pad_lens, cfg,
                      k_col=None):
    """Block-causal attention of ``q`` (B, Tq, H, D) over ``keys`` /
    ``values`` (B, N, kv_width) at positions ``k_pos`` (B, N) or (N,), for a
    config with ``dtype``, ``n_kv_head``, ``head_dim`` and ``block_length``
    (1 is plain causal attention). ``k_col`` (N,), where given, are the
    keys' cache columns, of which the first ``pad_lens`` of a row are left
    padding."""
    dt, g, d = cfg.dtype, cfg.n_kv_head, cfg.head_dim
    b, t, h, _ = q.shape
    n = keys.shape[1]
    with jax.named_scope("attn_core"):
        visible = block_causal(
            q_pos, jnp.broadcast_to(k_pos, (b, n)), cfg.block_length
        )
        if pad_lens is not None:
            col = jnp.arange(n) if k_col is None else k_col
            visible = visible & (col[None, None, :] >= pad_lens[:, None, None])
        # Group by group, on 128-lane slices of the keys and values as
        # they lie (a token's groups side by side in one vector): one
        # product over (B, N, g, D) would first move g ahead of N, a
        # copy of everything the pass gathered.
        qg = q.reshape(b, t, g, h // g, d)
        out = []
        for i in range(g):
            lanes = slice(i * d, (i + 1) * d)
            s = jnp.einsum(
                "btrd,bnd->brtn", qg[:, :, i], keys[..., lanes].astype(dt),
                preferred_element_type=jnp.float32,
            ) * d ** -0.5
            p = jax.nn.softmax(
                jnp.where(visible[:, None], s, -1e30), axis=-1
            ).astype(dt)
            out.append(jnp.einsum(
                "brtn,bnd->btrd", p, values[..., lanes].astype(dt),
                preferred_element_type=jnp.float32,
            ).astype(dt))
        return jnp.stack(out, axis=2).reshape(b, t, h, d)


def paged_attention(q, k, v, pools, layer, positions, pad_lens, slot_index,
                    page_table, cfg):
    """The serving engine's page pool (GPT-2's ``_paged_attention``): row
    b's T new keys and values land at logical columns ``slot_index[b] + t``
    through its table, columns beyond the table and dead rows in the
    layer's trash page 0; each row then reads the pages its table names,
    its own block's among them, and nothing else. Both pools are (layers,
    kv_pages, page_size, kv_width), a whole number of 128-lane rows a token
    (``ops/paged_pool.py``)."""
    ps = cfg.kv_page_size
    first_page = layer * cfg.kv_pages
    _, flat = paged_pool.token_slots(
        page_table, slot_index, q.shape[1], ps, first_page
    )
    with jax.named_scope("kv_write"):
        pools = tuple(
            paged_pool.write_tokens(pool, flat, new)
            for pool, new in zip(pools, (k, v))
        )
    with jax.named_scope("kv_read"):
        keys, values = (
            paged_pool.read_rows(pool, first_page + page_table, (cfg.kv_width,))
            for pool in pools
        )
    cols = jnp.arange(page_table.shape[1] * ps)
    pads = 0 if pad_lens is None else pad_lens[:, None]
    return grouped_attention(
        q, keys, values, positions, cols[None, :] - pads, pad_lens, cfg
    ), pools


def row_attention(q, k, v, rows, layer, positions, pad_lens, start, cfg):
    """Contiguous rows (layers, B, n_ctx, kv_width), the admission
    prefill's: the chunk's keys and values written at ``start``; a fresh
    multi-token chunk attends over itself, anything else over the rows."""
    t = q.shape[1]
    with jax.named_scope("kv_write"):
        rows = tuple(
            lax.dynamic_update_slice(
                row, new.astype(row.dtype)[None], (layer, 0, start, 0)
            )
            for row, new in zip(rows, (k, v))
        )
    pads = 0 if pad_lens is None else pad_lens[:, None]

    def over_rows():
        with jax.named_scope("kv_read"):
            keys, values = (
                lax.dynamic_index_in_dim(row, layer, 0, keepdims=False)
                for row in rows
            )
        cols = jnp.arange(cfg.n_ctx)
        visible_cols = jnp.where(cols < start + t, cols, 2 * cfg.n_ctx)
        return grouped_attention(
            q, keys, values, positions, visible_cols[None, :] - pads,
            pad_lens, cfg, k_col=cols,
        )

    def fresh():
        return grouped_attention(q, k, v, positions, positions, pad_lens, cfg)

    if t > 1:
        return lax.cond(start == 0, fresh, over_rows), rows
    return over_rows(), rows


class Layer(nn.Module):
    """One layer on the stream ``x`` (B, T, C) float32. ``cache`` is the
    whole (keys, values) cache threaded through: pools (layers, kv_pages,
    page_size, kv_width) read and written through ``page_table``, or rows
    (layers, B, n_ctx, kv_width) written at ``start``; ``layer`` is this
    layer's index into both and into ``experts`` (gate_up, down), every
    layer's, which the caller holds."""

    config: SdarConfig

    def _p(self, name, shape, init=None):
        init = init or nn.initializers.normal(0.02)
        return self.param(name, init, shape, self.config.dtype)

    @nn.compact
    def __call__(self, x, cache, layer, positions, valid, experts, *,
                 decode: bool, pad_lens=None, slot_index=None,
                 page_table=None, start=None):
        cfg = self.config
        c, ones = cfg.hidden_size, nn.initializers.ones
        a, cache = self._attention(
            rms_norm(x, self._p("attn_norm", (c,), ones), cfg.rms_norm_eps),
            cache, layer, positions, decode, pad_lens, slot_index,
            page_table, start,
        )
        h = x + a
        y, sizes = self._moe(
            rms_norm(h, self._p("mlp_norm", (c,), ones), cfg.rms_norm_eps),
            valid, experts, layer,
        )
        return h + y, cache, sizes

    def _moe(self, x, valid, experts, layer):
        cfg = self.config
        b, t, c = x.shape
        flat = x.reshape(b * t, c)
        with jax.named_scope("router"):
            idx, w = route(flat, self._p("router", (c, cfg.n_experts)), cfg)
        with jax.named_scope("moe_experts"):
            y, sizes = routed_experts(
                flat, idx, w, valid.reshape(-1), *experts, cfg.dtype, layer
            )
        return y.reshape(b, t, c), sizes

    def _attention(self, x, cache, layer, positions, decode, pad_lens,
                   slot_index, page_table, start):
        cfg = self.config
        dt, eps = cfg.dtype, cfg.rms_norm_eps
        b, t, c = x.shape
        h, g, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        out_init = nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer))
        ones = nn.initializers.ones
        with jax.named_scope("attn_q"):
            q = dot(x, self._p("q", (c, h * d)), dt).reshape(b, t, h, d)
            q = rotate_half(
                rms_norm(q, self._p("q_norm", (d,), ones), eps), positions,
                cfg.rope_theta,
            )
        with jax.named_scope("kv_write"):
            k = dot(x, self._p("k", (c, g * d)), dt).reshape(b, t, g, d)
            k = rotate_half(
                rms_norm(k, self._p("k_norm", (d,), ones), eps), positions,
                cfg.rope_theta,
            ).reshape(b, t, g * d)
            v = dot(x, self._p("v", (c, g * d)), dt)
        if not decode:
            a = grouped_attention(q, k, v, positions, positions, pad_lens, cfg)
        elif page_table is not None:
            a, cache = paged_attention(
                q, k, v, cache, layer, positions, pad_lens, slot_index,
                page_table, cfg,
            )
        else:
            a, cache = row_attention(
                q, k, v, cache, layer, positions, pad_lens, start, cfg
            )
        with jax.named_scope("attn_out"):
            return dot(
                a.reshape(b, t, h * d), self._p("o", (h * d, c), out_init),
                dt, jnp.float32,
            ), cache

class _ScanLayer(nn.Module):
    """Scan-body adapter: carry (x, cache), scanned input the layer's index,
    scanned output the tokens each expert got."""

    config: SdarConfig

    @nn.compact
    def __call__(self, carry, layer, positions, valid, experts, decode,
                 pad_lens, slot_index, page_table, start):
        x, cache = carry
        x, cache, sizes = Layer(self.config, name="layer")(
            x, cache, layer, positions, valid, experts, decode=decode,
            pad_lens=pad_lens, slot_index=slot_index, page_table=page_table,
            start=start,
        )
        return (x, cache), sizes


class Sdar(nn.Module):
    config: SdarConfig = SdarConfig()

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, decode: bool = False,
                 pad_lens=None, prefill: bool = False, slot_index=None,
                 page_table=None, head: bool = True):
        """See the module docstring. ``train`` changes nothing (no dropout;
        one precision on every path). ``prefill`` computes the head for the
        last position alone (an admission reads no other). ``head=False``
        returns None in the logits' place."""
        cfg = self.config
        del train
        b, t = tokens.shape
        c = cfg.hidden_size
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
        cache, start = (jnp.zeros((), cfg.dtype),) * 2, jnp.int32(0)
        offset = jnp.arange(t)[None, :]
        pads = jnp.zeros((b, 1), jnp.int32) if pad_lens is None else pad_lens[:, None]
        valid = offset >= pads
        if paged:
            leaf = (cfg.n_layer, cfg.kv_pages, cfg.kv_page_size,
                    paged_pool.token_width(cfg.kv_width))
            store = [
                self.variable("cache", name, jnp.zeros, leaf, cfg.dtype)
                for name in ("k", "v")
            ]
            # Kept beside them so that a pool and a prefill row have one
            # structure (the engine's insert maps the two together).
            self.variable("cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
            positions = slot_index[:, None] + offset - pads
            # A row whose table is all trash is dead (the engine zeroes it).
            valid = jnp.broadcast_to(page_table[:, :1] != 0, (b, t))
        elif decode:
            leaf = (cfg.n_layer, b, cfg.n_ctx, cfg.kv_width)
            store = [
                self.variable("cache", name, jnp.zeros, leaf, cfg.dtype)
                for name in ("k", "v")
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
        positions = jnp.clip(positions, 0, None)

        x = embed[tokens].astype(jnp.float32)
        experts = (
            self.param("experts_gate_up", nn.initializers.normal(0.02),
                       (cfg.n_layer, cfg.n_experts, c,
                        2 * cfg.moe_intermediate_size), cfg.dtype),
            self.param(
                "experts_down",
                nn.initializers.normal(0.02 / math.sqrt(2 * cfg.n_layer)),
                (cfg.n_layer, cfg.n_experts, cfg.moe_intermediate_size, c),
                cfg.dtype,
            ),
        )
        scan = nn.scan(
            _ScanLayer, variable_axes={"params": 0},
            split_rngs={"params": True}, length=cfg.n_layer,
            in_axes=(0,) + (nn.broadcast,) * 8,
        )
        (x, cache), sizes = scan(cfg, name="layers")(
            (x, cache), jnp.arange(cfg.n_layer), positions, valid, experts,
            decode, pad_lens, slot_index, page_table, start,
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
        if not head:
            return None
        norm_f = self.param("norm_f", nn.initializers.ones, (c,), cfg.dtype)
        lm_head = self.param(
            "lm_head", nn.initializers.normal(0.02), (c, cfg.vocab_size), cfg.dtype
        )
        if prefill:
            x = x[:, -1:]
        with jax.named_scope("lm_head"):
            return jnp.einsum(
                "btc,cv->btv",
                rms_norm(x, norm_f, cfg.rms_norm_eps).astype(cfg.dtype),
                lm_head.astype(cfg.dtype), preferred_element_type=jnp.float32,
            )
