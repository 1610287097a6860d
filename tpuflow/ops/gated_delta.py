"""The gated delta rule (Gated DeltaNet; the linear-attention layers of
``models/hybrid.py``), per head, with a state ``S`` of (key dim, value
dim), the two free to differ (Olmo-Hybrid's 96 × 192):

    S ← e^g · S;   δ = β (v − Sᵀk);   S ← S + k δᵀ;   o = Sᵀq

for every token in order, ``g ≤ 0`` the log of the decay and ``β`` in (0,
2) the write strength: up to 1 a model whose transition ``I − β k kᵀ``
keeps eigenvalues in [0, 1] (Qwen3-Next), up to 2 one that lets them reach
−1 (fla's ``allow_neg_eigval``, Olmo-Hybrid). The chunked form's
triangular solve then meets entries of ``A`` up to 2 in magnitude; on
keys as the models make them it agrees with the recurrence there as it
does below 1 (``tests/test_olmo_hybrid.py``). Three forms of it, one
result:

- ``chunked``: a prompt at once, ``chunk`` tokens at a time. Within a chunk
  every product is a dense one (the WY form of the delta rule: the chunk's
  updates folded into ``T = (I + A)⁻¹`` with ``A`` the chunk's strictly
  lower ``β k·k`` products under their decays), and only the chunk
  boundaries run in order, a scan over ``T / chunk`` steps: a prefill of
  3,000 tokens takes 47 sequential steps a layer where the recurrence takes
  3,000.
- ``step``: one token a row, the decode step: the state read once and
  written once, its two products taken off the old state
  (``o = e^g Sᵀq + δ (k·q)``).
- ``step_in_place``: ``step`` on the rows of a serving engine's leaf of S
  (layers, slots, heads, dk, dv), each row's S advanced where it lies by
  slot, in one Pallas kernel that takes the leaf as its own output: no
  gather of the rows, no scatter back and no S of the call's own, so a
  live row's S crosses memory once each way. The rows are visited sorted
  by slot, the trash row (the last, where dead rows write) after every
  live one: the kernel's pipeline fetches and writes back a block only
  when the next visit names another, so the dead rows share one visit's
  traffic, and the first of them alone computes. On the TPU backend
  (``in_place``) the decode step takes this form; elsewhere the gather,
  ``step`` and the scatter, and the tests run the kernel through Pallas's
  interpreter.

The layer's short causal conv keeps its state beside S, a row of the
last inputs a slot, and ``conv_step_in_place`` advances it the same way
in decode. Both kernels keep their leaf in HBM: a leaf small enough for
the chip's fast memory (the conv's, 38 to 81 MB in the serving cells) was
otherwise staged through it whole around every layer's update.

All keep ``S`` and their arithmetic in float32, the products at the
highest matmul precision or, in the kernel, as float32 sums on the vector
unit. A token with ``g = 0`` and ``β = 0`` (and ``q``, ``k``, ``v``
zero, a left pad) leaves ``S`` as it was, which is what makes a left-padded
prompt's final state that of the prompt alone. The caller normalises ``q``
and ``k``, scales ``q`` and brings both to the value heads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = lax.Precision.HIGHEST
CHUNK = 64


def _mm(spec, *ops):
    return jnp.einsum(spec, *ops, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def recurrent(q, k, v, g, beta, state):
    """The rule token by token, as written above (tests; a reference for
    the two forms). Shapes as ``chunked``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def one(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None, None]
        delta = bt[..., None] * (vt - _mm("bhkv,bhk->bhv", s, kt))
        s = s + kt[..., :, None] * delta[..., None, :]
        return s, _mm("bhkv,bhk->bhv", s, qt)

    xs = tuple(jnp.moveaxis(f32(a), 1, 0) for a in (q, k, v, g, beta))
    state, o = lax.scan(one, f32(state), xs)
    return jnp.moveaxis(o, 0, 1), state


def chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """``q``, ``k`` (B, T, H, Dk), ``v`` (B, T, H, Dv), ``g`` and ``beta``
    (B, T, H), ``state`` (B, H, Dk, Dv) the state before the first token.
    Returns (``o`` (B, T, H, Dv) float32, the state after the last token).
    A length that is not a multiple of ``chunk`` is padded at its end with
    tokens that change nothing."""
    b, t, h, dk = k.shape
    n = -(-t // chunk)
    pad = n * chunk - t

    def blocks(a):  # (B, T, H, ...) -> (B, H, N, C, ...) float32
        a = jnp.pad(a.astype(jnp.float32),
                    [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = (blocks(a) for a in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # (B, H, N, C): the decay from the chunk's start
    i = jnp.arange(chunk)
    lower = i[:, None] >= i[None, :]
    # e^(G_i − G_j) where j ≤ i, nought above the diagonal (masked before the
    # exponent: G_i − G_j > 0 there and may overflow).
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf))
    kb = k * beta[..., None]
    a = jnp.where(i[:, None] > i[None, :], _mm("...id,...jd->...ij", kb, k) * decay, 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    tri = lax.linalg.triangular_solve(
        eye + a, jnp.broadcast_to(eye, a.shape), left_side=True, lower=True,
        unit_diagonal=True,
    )  # (I + A)^-1
    u = _mm("...ij,...jd->...id", tri, v * beta[..., None])
    w = _mm("...ij,...jd->...id", tri, kb * jnp.exp(gc)[..., None])
    qk = _mm("...id,...jd->...ij", q, k) * decay
    last = gc[..., -1:]  # (B, H, N, 1)
    k_out = k * jnp.exp(last - gc)[..., None]  # each key decayed to the chunk's end

    def one(s, x):
        qc, qk_c, u_c, w_c, k_c, gc_c, last_c = x
        v_new = u_c - _mm("bhcd,bhdv->bhcv", w_c, s)
        o = _mm("bhcd,bhdv->bhcv", qc * jnp.exp(gc_c)[..., None], s) + _mm(
            "bhij,bhjv->bhiv", qk_c, v_new
        )
        s = s * jnp.exp(last_c)[..., None] + _mm("bhcd,bhcv->bhdv", k_c, v_new)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (q, qk, u, w, k_out, gc, last))
    state, o = lax.scan(one, state.astype(jnp.float32), xs)
    o = jnp.moveaxis(o, 0, 2)  # (B, H, N, C, Dv)
    o = jnp.moveaxis(o.reshape(b, h, n * chunk, -1), 1, 2)
    return o[:, :t], state


def step(q, k, v, g, beta, state):
    """One token a row: ``q``, ``k`` (B, H, Dk), ``v`` (B, H, Dv), ``g``
    and ``beta`` (B, H), ``state`` (B, H, Dk, Dv). Returns (``o`` (B, H,
    Dv) float32, the new state)."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    s = state.astype(jnp.float32)
    decay = jnp.exp(g)[..., None]
    kv = decay * _mm("bhkv,bhk->bhv", s, k)
    qv = decay * _mm("bhkv,bhk->bhv", s, q)
    delta = beta[..., None] * (v - kv)
    o = qv + delta * jnp.sum(k * q, axis=-1, keepdims=True)
    s = s * decay[..., None] + k[..., :, None] * delta[..., None, :]
    return o, s


def in_place() -> bool:
    """Whether the decode step advances S and the conv state where they lie
    (``step_in_place``, ``conv_step_in_place``): on the TPU backend alone."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    """Pallas interpret mode everywhere but on the TPU backend (the CPU
    tests run the kernels' exact programs through the interpreter)."""
    return jax.default_backend() != "tpu"


def _visits(slots):
    """The call's rows in the order the kernels visit them: sorted by slot,
    so the trash row (the leaf's last, every dead row's) comes after every
    live one. Returns (each visit's slot, its row of the call)."""
    return lax.sort(
        (slots.astype(jnp.int32), jnp.arange(slots.shape[0], dtype=jnp.int32)),
        num_keys=1,
    )


# The most of a row's S that one visit of the kernel moves: its block in
# and out, each double-buffered, then take 8 MiB of VMEM, half of what the
# compiler scopes by default (the call's q, k, v, o and scalars take at
# most 5 MiB more at 64 rows). On a TPU v5e a call at 8 live rows of 16
# took 71 us (Qwen3-Next's rows; 41 at the memory peak).
BLOCK_BYTES = 2**21


def _head_block(h: int, dk: int, dv: int) -> int:
    """The heads a visit takes: the most that divide ``h`` and whose S
    fits ``BLOCK_BYTES`` as VMEM holds it (8-row, 128-lane tiles: 32 of
    128 x 128, 15 of 96 x 192 in 96 x 256)."""
    head = -(-dk // 8) * 8 * -(-dv // 128) * 128 * 4
    return max((d for d in range(1, h + 1) if h % d == 0 and d * head <= BLOCK_BYTES),
               default=1)


def _step_kernel(layer_ref, slot_ref, row_ref, c_ref, q_ref, k_ref, v_ref,
                 s_ref, o_ref, out_ref):
    """One visit: a row's block of hb heads, ``step``'s arithmetic on all
    of them at once. ``q_ref`` / ``k_ref`` (B, H / hb, hb, dk), ``v_ref`` /
    ``o_ref`` (B, H / hb, hb, dv) and ``c_ref`` (B, H / hb, hb, 3: e^g, β,
    k·q), every row's, held in VMEM for the whole call; ``s_ref`` /
    ``out_ref`` (hb, dk, dv), the visit's block of S. A visit to the slot
    the one before it took (a dead row after the first at the trash row)
    leaves the block as that one made it and reads o = 0."""
    del layer_ref
    block, r = pl.program_id(0), pl.program_id(1)
    row = row_ref[r]
    repeat = (r > 0) & (slot_ref[r] == slot_ref[jnp.maximum(r - 1, 0)])

    @pl.when(repeat)
    def _():
        o_ref[row, block] = jnp.zeros(o_ref.shape[2:], o_ref.dtype)

    @pl.when(jnp.logical_not(repeat))
    def _():
        s = s_ref[...]
        # A head's k and q down the key axis, its scalars (hb, 1).
        k, q = k_ref[row, block][:, :, None], q_ref[row, block][:, :, None]
        decay, beta, kq = (c_ref[row, block][:, i:i + 1] for i in range(3))
        kv = decay * jnp.sum(s * k, axis=1)
        qv = decay * jnp.sum(s * q, axis=1)
        delta = beta * (v_ref[row, block] - kv)
        o_ref[row, block] = qv + delta * kq
        out_ref[...] = s * decay[:, :, None] + k * delta[:, None, :]


def step_in_place(leaf, layer, slots, q, k, v, g, beta):
    """``step`` for the rows whose S lies in ``leaf`` (layers, slots, H,
    Dk, Dv) float32 at ``[layer, slots]``: ``slots`` (B,) each row's slot,
    the dead rows' the trash row (the leaf's last); ``q``, ``k`` (B, H,
    Dk), ``v`` (B, H, Dv), ``g`` and ``beta`` (B, H). Returns (``o`` (B, H,
    Dv) float32, the leaf with those rows advanced and every other row as
    it was). Rows that share a slot (only the dead rows, at the trash row)
    leave it as the first of them makes it, and the others read o = 0."""
    n, rows, h, dk, dv = leaf.shape
    if leaf.dtype != jnp.float32:
        raise ValueError(f"the leaf of S is float32, not {leaf.dtype}")
    b = slots.shape[0]
    hb = _head_block(h, dk, dv)
    nh = h // hb
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    slot, row = _visits(slots)
    c = jnp.stack([jnp.exp(g), beta, jnp.sum(k * q, axis=-1)], axis=-1)

    # S's index map takes the grid's (head block, visit), then the
    # prefetched scalars: the layer, each visit's slot and its row of the
    # call. The call's q, k, v and o lie whole in VMEM: a visit moves S
    # alone.
    state = pl.BlockSpec((None, None, hb, dk, dv),
                         lambda i, r, layer, slot, row: (layer[0], slot[r], i, 0, 0))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    o, leaf = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nh, b),
            in_specs=[whole, whole, whole, whole, state],
            out_specs=[whole, state],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, nh, hb, dv), jnp.float32),
                   pltpu.HBM(leaf.shape, leaf.dtype)],
        input_output_aliases={7: 1},
        # Every row's S in and out, as if every row were live; about eight
        # operations a number of it.
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * dk * dv, transcendentals=0,
            bytes_accessed=2 * b * h * dk * dv * 4,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=_interpret(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slot, row, c.reshape(b, nh, hb, 3),
      q.reshape(b, nh, hb, dk), k.reshape(b, nh, hb, dk), v.reshape(b, nh, hb, dv), leaf)
    return o.reshape(b, h, dv), leaf


# The rows of the conv state that a visit moves: the chip's memory lies in
# tiles of 8 rows, and a slot's row of the leaf (layers, slots, taps x
# channels) is one row of a tile, so a visit takes the tile that holds it.
CONV_TILE = 8


def _conv_kernel(layer_ref, slot_ref, row_ref, x_ref, w_ref, s_ref, out_ref, new_ref):
    """One visit: a row's input ``x_ref`` (1, C) and the tile of the conv
    state that holds its slot, ``s_ref`` / ``new_ref`` (``CONV_TILE``, taps
    x C); ``w_ref`` (taps + 1, C) the conv's weights. The first visit to a
    tile takes it as it lies; each visit then writes its own row and its
    output, ``out_ref`` (1, C), before the conv's activation."""
    del layer_ref, row_ref
    r = pl.program_id(0)
    slot = slot_ref[r]

    @pl.when((r == 0) | (slot // CONV_TILE != slot_ref[jnp.maximum(r - 1, 0)] // CONV_TILE))
    def _():
        new_ref[...] = s_ref[...]

    c, width = x_ref.shape[-1], w_ref.shape[0]
    tile = s_ref[...]
    mine = lax.broadcasted_iota(jnp.int32, tile.shape, 0) == slot % CONV_TILE
    prev = jnp.sum(jnp.where(mine, tile, 0.0), axis=0, keepdims=True)
    taps = [prev[:, j * c:(j + 1) * c] for j in range(width - 1)] + [x_ref[...]]
    out = taps[0] * w_ref[0:1]
    for j in range(1, width):
        out = out + taps[j] * w_ref[j:j + 1]
    out_ref[...] = out
    new_ref[...] = jnp.where(mine, jnp.concatenate(taps[1:], axis=1), new_ref[...])


def conv_step_in_place(leaf, layer, slots, x, w):
    """A Gated DeltaNet layer's short causal conv for one token a row, its
    state advanced where it lies: ``leaf`` (layers, slots, (taps) x C)
    float32, a row the last ``taps`` inputs, oldest first; ``slots`` (B,)
    as in ``step_in_place``; ``x`` (B, C) the token's input, ``w`` (taps +
    1, C). Returns (the conv's output ``Σ_j w[j] · window[j]`` over the
    window of the row's state and ``x``, (B, C) float32; the leaf with
    those rows' state moved on by ``x`` and every other row as it was)."""
    n, rows, rc = leaf.shape
    if leaf.dtype != jnp.float32:
        raise ValueError(f"the conv state is float32, not {leaf.dtype}")
    b, c = x.shape
    slot, row = _visits(slots)
    tile = pl.BlockSpec((None, CONV_TILE, rc),
                        lambda r, layer, slot, row: (layer[0], slot[r] // CONV_TILE, 0))
    one = pl.BlockSpec((None, 1, c), lambda r, layer, slot, row: (row[r], 0, 0))
    out, leaf = pl.pallas_call(
        _conv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[one, pl.BlockSpec(memory_space=pltpu.VMEM), tile],
            out_specs=[one, tile],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
                   pltpu.HBM(leaf.shape, leaf.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slot, row,
      x.astype(jnp.float32).reshape(b, 1, c), w.astype(jnp.float32), leaf)
    return out.reshape(b, c), leaf
