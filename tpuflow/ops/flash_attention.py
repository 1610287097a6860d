"""Blockwise / flash attention: O(T) memory attention for TPU.

Two tiers with identical numerics:

- ``blockwise_attention`` — pure-JAX online-softmax attention via ``lax.scan``
  over KV chunks. O(block) memory instead of O(T^2), differentiable, runs on
  any backend; the building block of ring attention.
- ``flash_attention`` — Pallas TPU kernels: scores live in VMEM as float32
  tiles and are never written to HBM. The forward saves only (O,
  logsumexp); ONE backward kernel recomputes P a tile at a time and feeds
  dq, dk and dv from it — the flash-style compute-for-memory trade. Block
  sizes and the heads a program owns follow from the shape alone (see the
  kernels' section comment).

The reference has no attention anywhere (its model is an image MLP,
my_ray_module.py:94-112); these exist for the GPT-2 acceptance config and
first-class long-context support (SURVEY.md §5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# The names on the forward kernel's two outputs (``_flash_fwd``): the
# residual a rematerialised block keeps, so its recompute holds no kernel
# (``models/gpt2.py`` ``remat_saves``).
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _interpret() -> bool:
    """Pallas interpret mode everywhere but on the TPU backend (the CPU
    tests run the kernels' exact program through the interpreter)."""
    return jax.default_backend() != "tpu"


def _chunk_positions(t: int, block: int):
    n = t // block
    return jnp.arange(n)[:, None] * block + jnp.arange(block)[None, :]


def blockwise_attention(q, k, v, *, causal: bool = True, block_k: int = 512):
    """Online-softmax attention, scanning KV in chunks. q,k,v: (B,T,H,D)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    block_k = min(block_k, Tk)
    if Tk % block_k:
        return _reference_attention(q, k, v, causal=causal)
    nk = Tk // block_k
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))

    q32 = q.astype(jnp.float32)
    kc = k.reshape(B, nk, block_k, H, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, block_k, H, D).transpose(1, 0, 2, 3, 4)
    k_pos = _chunk_positions(Tk, block_k)
    q_pos = jnp.arange(Tq)

    def body(carry, inp):
        m, l, acc = carry
        k_blk, v_blk, kp = inp
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32)
        ) * scale
        if causal:
            mask = q_pos[:, None] >= kp[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, k_pos))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _reference_attention(q, k, v, *, causal: bool):
    from tpuflow.ops.attention import xla_attention

    return xla_attention(q, k, v, causal=causal)


# ---------------------------------------------------------- pallas kernels
# One forward and one backward kernel, shaped by the sequence lengths
# (ISSUE 31). What the shape decides, all of it in `_block_sizes` and
# `_head_group`:
#
# - A program owns a GROUP of heads whose head dims fill the 128 lanes
#   (two heads of 64) and reads q/k/v straight out of the model's
#   (B, T, H·D) layout: no (B,T,H,D) <-> (B·H,T,D) transposes around the
#   call, every block and accumulator 128 lanes wide. A head's products
#   contract over / land in its own lanes by zeroing the other heads'
#   lanes of ONE operand — on a 128-deep MXU that costs what the 64-deep
#   product cost, and nothing is sliced or concatenated along lanes.
# - Up to 1,024 positions a program holds the whole row of K and V (256 KB
#   each in bf16 at two heads of 64), so the grid is (B, H/group, 1, 1) and
#   the walk over score tiles is a static loop inside the kernel: tiles
#   above the diagonal do not exist, tiles below it carry no mask. Longer
#   rows walk 1,024-position blocks on two more grid axes with the online
#   softmax (forward) / the accumulators (backward) carried in scratch.
# - Scores live in VMEM as float32 tiles of `_SUB` positions a side; the
#   softmax statistics are float32, probabilities feed the MXU in the
#   input dtype. Both kernels work on TRANSPOSED tiles (k·qᵀ: rows = k
#   positions, columns = q positions), so every per-query statistic — the
#   running max and denominator, lse, D = rowsum(dO ∘ O) — is a ROW:
#   reductions run down the sublanes, the residual is (B, H, T) and not
#   broadcast over 128 lanes, and in the backward dv += pᵀ·dO and
#   dk += dSᵀ·q are plain products (only p·v and dS·k take a transposed
#   operand).
# - The backward is ONE kernel: per tile it recomputes p from (q, k, lse)
#   once and feeds all three gradients (five products; a dq kernel beside
#   a dk/dv kernel recomputes p in each, seven). D is one XLA reduction
#   outside.
# - The compiler schedules a program's tiles in the order they are
#   written, so both kernels issue the NEXT tile's score products before
#   the current tile's elementwise work: the MXU and the VPU overlap
#   (forward 0.77 -> 0.64 ms at the training cell's shape, chip call 90).
_LANES = 128
_SUB = 256  # score tile (rows and columns) a program works on at a time
_BLOCKS = (1024, 512, 256)  # row blocks a grid step may hold, largest first

_NN = (((1,), (0,)), ((), ()))  # a · b
_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b


def _block_sizes(tq: int, tk: int) -> tuple[int, int, int, int]:
    """(block_q, block_k, sub_q, sub_k) for these sequence lengths: the
    largest of ``_BLOCKS`` that divides a length (the whole row up to
    1,024 positions), a short row as one block; score tiles of ``_SUB``."""
    def block(t):
        return next((b for b in _BLOCKS if t % b == 0), t)

    bq, bk = block(tq), block(tk)
    return bq, bk, min(_SUB, bq), min(_SUB, bk)


def _head_group(h: int, d: int) -> int | None:
    """Heads a program owns: one where a head fills whole 128-lane tiles
    by itself, else the heads that fill one tile together (two heads of
    64), else all of them where all fit one tile (a block's minor
    dimension may always be the array's own). None where none of these
    holds (25 heads of 64, heads of 96): a wider group would contract
    every head's products over all its lanes and hold a groups-squared
    accumulator, and the kernels do not run such a shape."""
    if d % _LANES == 0 or h == 1:
        return 1
    if _LANES % d == 0 and h % (_LANES // d) == 0:
        return _LANES // d
    return h if h * d <= _LANES else None


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _only_head(x, h: int, d: int, heads: int):
    """x with every lane outside head h's [h·d, (h+1)·d) zeroed."""
    if heads == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where(
        (lane >= h * d) & (lane < (h + 1) * d), x, jnp.zeros_like(x)
    )


def _col(row):
    """(1, n) row -> (n, 1) column, through a (128, n) -> (n, 128)
    transpose (the shape of transpose the chip does natively)."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _causal(st, k0: int, q0: int):
    """A transposed score tile whose rows start at k position k0 and
    columns at q position q0, with every k after its q masked out."""
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    return jnp.where(k_pos <= q_pos, st, _NEG_INF)


def _pipelined(steps, produce, consume):
    """consume(step, produce(step)) for every step, with the next step's
    products issued before the current step's elementwise work."""
    cur = produce(*steps[0])
    for n, step in enumerate(steps):
        nxt = produce(*steps[n + 1]) if n + 1 < len(steps) else None
        consume(*step, cur)
        cur = nxt


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal, sub_q,
                heads, d, with_lse, square):
    """One (batch, head group, q block, kv block) step of the forward.

    Blocks: q/o (1, block_q, heads·d), k/v (1, block_k, heads·d), lse
    (1, 1, heads, block_q). Scratch per head: the running max and
    denominator as (1, block_q) rows and the output accumulator (block_q,
    heads·d, zero outside the head's lanes). ``square`` says the grid has
    one q and one kv step: the whole causal triangle is this tile."""
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(diagonal: bool):
        vms = [_only_head(v_ref[0], h, d, heads) for h in range(heads)]

        def pieces(i):
            # The k rows q tile i sees, as (start, height, masked):
            # everything before the diagonal tile in one unmasked piece,
            # then the diagonal tile.
            if not diagonal:
                return [(0, bk, False)]
            r0 = i * sub_q
            return ([(0, r0, False)] if r0 else []) + [(r0, sub_q, True)]

        def scores(h, i):
            r0 = i * sub_q
            qm = _only_head(q_ref[0, r0:r0 + sub_q, :], h, d, heads)
            out = []
            for k0, height, masked in pieces(i):
                st = _dot(k_ref[0, k0:k0 + height, :], qm, _NT) * scale
                out.append(_causal(st, k0, r0) if masked else st)
            return out

        def softmax_pv(h, i, sts):
            cols = slice(i * sub_q, (i + 1) * sub_q)
            vm = vms[h]
            m_prev = m_scr[h, :, cols]
            m_new = m_prev
            for st in sts:
                m_new = jnp.maximum(m_new, st.max(axis=0, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_scr[h, :, cols]
            pv = None
            for st, (k0, height, _) in zip(sts, pieces(i)):
                pt = jnp.exp(st - m_new)
                l_new = l_new + pt.sum(axis=0, keepdims=True)
                part = _dot(pt.astype(vm.dtype), vm[k0:k0 + height], _TN)
                pv = part if pv is None else pv + part
            if square:  # nothing carried in: the accumulator is pv
                acc_scr[h, cols, :] = pv
            else:
                acc_scr[h, cols, :] = acc_scr[h, cols, :] * _col(corr) + pv
            m_scr[h, :, cols] = m_new
            l_scr[h, :, cols] = l_new

        _pipelined(
            [(h, i) for i in range(bq // sub_q) for h in range(heads)],
            scores, softmax_pv,
        )

    if not causal:
        tile(False)
    elif square:
        tile(True)
    else:
        # Tiles above the diagonal are skipped (and, by the index maps,
        # never fetched); those below it are unmasked.
        pl.when(ik == iq)(lambda: tile(True))
        pl.when(ik < iq)(lambda: tile(False))

    @pl.when(ik == nk - 1)
    def _final():
        out = None
        for h in range(heads):
            l = jnp.maximum(l_scr[h], 1e-30)
            part = acc_scr[h] * _col(1.0 / l)
            out = part if out is None else out + part
            if lse_ref is not None:
                lse_ref[0, 0, h:h + 1, :] = m_scr[h] + jnp.log(l)
        o_ref[0] = out.astype(o_ref.dtype)


def _rows(x):
    """(B, T, H, D) -> (B, T, H·D): free, the model's own layout."""
    B, T, H, D = x.shape
    return x.reshape(B, T, H * D)


def _compiler_params(block_elems: int, itemsize: int, scratch_elems: int):
    """The grid's semantics and the VMEM a program may use: its blocks
    (``block_elems`` of ``itemsize`` bytes, twice for the pipeline's
    double buffers), its float32 scratch, and the compiler's own default
    of 16 MiB for the score tiles and other temporaries, whose size no
    sequence length changes. In bfloat16 at two heads of 64: 19 MiB in
    the forward and 21 in the backward at T = 1,024, 28 in the backward
    at 8,192 (dq's whole row is resident)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=(
            2 * block_elems * itemsize + 4 * scratch_elems + 16 * 1024 * 1024
        ),
    )


def _flash_fwd(q, k, v, causal: bool, interpret: bool, *,
               with_lse: bool = False):
    """Forward kernel. Returns o (B, Tq, H, D), and with ``with_lse`` the
    softmax residual lse (B, H, Tq) float32 — the one row a head the
    backward needs to recompute p."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq, bk, sub_q, _ = _block_sizes(Tq, Tk)
    g = _head_group(H, D)
    gd = g * D
    nq, nk = Tq // bq, Tk // bk

    def kv_index(b, h, i, j):
        # A skipped tile re-names the block already held: no fetch.
        return (b, jnp.minimum(j, i) if causal else j, h)

    q_spec = pl.BlockSpec((1, bq, gd), lambda b, h, i, j: (b, i, h))
    kv_spec = pl.BlockSpec((1, bk, gd), kv_index)
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B, Tq, H * D), q.dtype)]
    if with_lse:
        out_specs.append(
            pl.BlockSpec((1, 1, g, bq), lambda b, h, i, j: (b, h, 0, i))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((B, H // g, g, Tq), jnp.float32)
        )
    res = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=1.0 / (D ** 0.5), causal=causal, sub_q=sub_q,
            heads=g, d=D, with_lse=with_lse, square=nq == 1 and nk == 1,
        ),
        grid=(B, H // g, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((g, 1, bq), jnp.float32),  # running max
            pltpu.VMEM((g, 1, bq), jnp.float32),  # running denominator
            pltpu.VMEM((g, bq, gd), jnp.float32),  # output accumulator
        ],
        compiler_params=_compiler_params(
            2 * (bq + bk) * gd, q.dtype.itemsize, g * bq * (gd + 2)
        ),
        interpret=interpret,
    )(_rows(q), _rows(k), _rows(v))
    if with_lse:
        # The forward the vjp rule calls: its two outputs are the residual a
        # rematerialised block keeps (``GPT2``'s ``remat_wrap``), so the
        # recompute holds no kernel. Named as the kernel writes them, whole
        # 128-lane rows: a stack of (..., H, D) the chip would pad.
        o, lse = map(checkpoint_name, res, RESIDUAL_NAMES)
        return o.reshape(B, Tq, H, D), lse.reshape(B, H, Tq)
    return res[0].reshape(B, Tq, H, D)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *, scale, causal,
                sub_q, sub_k, heads, d, square):
    """One (batch, head group, kv block, q block) step of the backward:
    dq, dk and dv of every score tile from one recompute of p.

    On transposed tiles: sᵀ = k·qᵀ, pᵀ = exp(sᵀ − lse), dv += pᵀ·dO,
    dpᵀ = v·dOᵀ, dSᵀ = pᵀ ∘ (dpᵀ − D), dk += dSᵀ·q, dq += (dSᵀ)ᵀ·k;
    lse and D are (1, sub_q) rows. dk/dv accumulate over the inner (q)
    axis, dq over the whole row in scratch across both."""
    jk, iq = pl.program_id(2), pl.program_id(3)
    nk, nq = pl.num_programs(2), pl.num_programs(3)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when((jk == 0) & (iq == 0))
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(iq == 0)
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(diagonal: bool):
        q0 = pl.multiple_of(iq * bq, bq)

        kms = [_only_head(k_ref[0], h, d, heads) for h in range(heads)]

        def products(h, i, j):
            rows = slice(i * sub_q, (i + 1) * sub_q)
            cols = slice(j * sub_k, (j + 1) * sub_k)
            qm = _only_head(q_ref[0, rows, :], h, d, heads)
            dom = _only_head(do_ref[0, rows, :], h, d, heads)
            st = _dot(k_ref[0, cols, :], qm, _NT) * scale
            return qm, dom, st, _dot(v_ref[0, cols, :], dom, _NT)

        def gradients(h, i, j, made):
            qm, dom, st, dpt = made
            r0, k0 = i * sub_q, j * sub_k
            rows, cols = slice(r0, r0 + sub_q), slice(k0, k0 + sub_k)
            if diagonal and k0 + sub_k - 1 > r0:  # straddles the diagonal
                st = _causal(st, k0, r0)
            pt = jnp.exp(st - lse_ref[0, 0, h:h + 1, rows])
            dv_scr[cols, :] += _dot(pt.astype(dom.dtype), dom, _NN)
            dst = (pt * (dpt - delta_ref[0, 0, h:h + 1, rows])).astype(
                qm.dtype
            )
            dk_scr[cols, :] += _dot(dst, qm, _NN)
            dq_scr[pl.ds(q0 + r0, sub_q), :] += _dot(dst, kms[h][cols], _TN)

        _pipelined(
            [
                (h, i, j)
                for h in range(heads)
                for i in range(bq // sub_q)
                for j in range(bk // sub_k)
                # a tile wholly above the diagonal does not exist
                if not (diagonal and j * sub_k > (i + 1) * sub_q - 1)
            ],
            products, gradients,
        )

    if not causal:
        tile(False)
    elif square:
        tile(True)
    else:
        pl.when(iq == jk)(lambda: tile(True))
        pl.when(iq > jk)(lambda: tile(False))

    @pl.when(iq == nq - 1)
    def _final_dkv():
        # The scale of dS = scale · p ∘ (dp − D) is applied to the row-sized
        # results, not to every score.
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((jk == nk - 1) & (iq == nq - 1))
    def _final_dq():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_fused(q, k, v, o, lse, g, causal: bool, interpret: bool):
    """The one-kernel backward (see the section comment).
    ``lse`` is the forward's (B, H, Tq) residual, ``g`` the cotangent of
    o."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq, bk, sub_q, sub_k = _block_sizes(Tq, Tk)
    hg = _head_group(H, D)
    gd = hg * D
    nq, nk = Tq // bq, Tk // bk
    # D_i = rowsum(dO ∘ O), once, as a row per head like lse.
    delta = jnp.einsum(
        "bthd,bthd->bht", g.astype(jnp.float32), o.astype(jnp.float32)
    )

    def per_group(x):  # (B, H, Tq) -> one (heads, Tq) tile a program
        return x.reshape(B, H // hg, hg, Tq)

    def q_index(b, h, j, i):
        return (b, jnp.maximum(i, j) if causal else i, h)

    def row_index(b, h, j, i):
        return (b, h, 0, jnp.maximum(i, j) if causal else i)

    q_spec = pl.BlockSpec((1, bq, gd), q_index)
    kv_spec = pl.BlockSpec((1, bk, gd), lambda b, h, j, i: (b, j, h))
    row_spec = pl.BlockSpec((1, 1, hg, bq), row_index)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=1.0 / (D ** 0.5), causal=causal, sub_q=sub_q,
            sub_k=sub_k, heads=hg, d=D, square=nq == 1 and nk == 1,
        ),
        grid=(B, H // hg, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[
            # dq's whole row stays resident across both inner axes.
            pl.BlockSpec((1, Tq, gd), lambda b, h, j, i: (b, 0, h)),
            kv_spec,
            kv_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tq, H * D), q.dtype),
            jax.ShapeDtypeStruct((B, Tk, H * D), k.dtype),
            jax.ShapeDtypeStruct((B, Tk, H * D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((Tq, gd), jnp.float32),
            pltpu.VMEM((bk, gd), jnp.float32),
            pltpu.VMEM((bk, gd), jnp.float32),
        ],
        compiler_params=_compiler_params(
            (2 * bq + 4 * bk + Tq) * gd, q.dtype.itemsize, (Tq + 2 * bk) * gd
        ),
        interpret=interpret,
    )(_rows(q), _rows(k), _rows(v), _rows(g), per_group(lse), per_group(delta))
    return (
        dq.reshape(B, Tq, H, D), dk.reshape(B, Tk, H, D),
        dv.reshape(B, Tk, H, D),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, causal):
    return _flash_fwd(q, k, v, causal, _interpret())


def _flash_vjp_fwd(q, k, v, causal):
    o, lse = _flash_fwd(q, k, v, causal, _interpret(), with_lse=True)
    # The residual is the (B, H, Tq) float32 row the backward kernel reads
    # as it is: 4 bytes a position and head. With o it is what a
    # rematerialised block keeps by name (``_flash_fwd``); q, k and v it
    # recomputes.
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, res, g):
    q, k, v, o, lse = res
    # Trace-time marker: which compiled programs took the fused backward
    # (each jit trace of a differentiated flash call lands here once).
    from tpuflow import obs

    block_q, block_k, _, _ = _block_sizes(q.shape[1], k.shape[1])
    obs.event(
        "ops.flash_bwd_fused", seq=int(q.shape[1]), heads=int(q.shape[2]),
        causal=bool(causal), block_q=block_q, block_k=block_k,
    )
    return _flash_bwd_fused(q, k, v, o, lse, g, causal, _interpret())


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_tiles(tq: int, tk: int, h: int, d: int, *,
                causal: bool = True) -> bool:
    """Whether the kernels can run this shape: each sequence length at
    most a score tile (256) or a multiple of it, head_dim a multiple of 8,
    heads that group into 128-lane tiles (``_head_group``), and under a
    causal mask one length (self-attention)."""
    if causal and tq != tk:
        return False
    if _head_group(h, d) is None:
        return False
    return not (tq % min(_SUB, tq) or tk % min(_SUB, tk) or d % 8)


def flash_attention(q, k, v, *, causal: bool = True):
    """Pallas TPU flash attention. q,k,v: (B,T,H,D) → (B,T,H,D).

    Block sizes and the heads a program owns follow from the shape alone
    (``_block_sizes``, ``_head_group``). A shape that does not tile
    (``flash_tiles``) raises on the TPU backend: the kernel was asked for
    by name, and ``impl='auto'`` is the spelling that may pick XLA. Off
    the TPU — interpret mode, tests — such a shape takes
    ``blockwise_attention``, the same math. Causal attention with
    Tq != Tk (a q block against a longer K/V row), which the kernels
    before ISSUE 31 ran and no caller used, is one such shape now.
    """
    Tq, H, D = q.shape[1:]
    Tk = k.shape[1]
    if not flash_tiles(Tq, Tk, H, D, causal=causal):
        if not _interpret():
            raise ValueError(
                f"flash attention cannot run q{tuple(q.shape)} "
                f"k{tuple(k.shape)}: sequence lengths must be at most "
                f"{_SUB} or multiples of it (and equal under a causal "
                f"mask), head_dim a multiple of 8, and the heads must "
                f"group into {_LANES}-lane tiles (head_dim a multiple of "
                f"{_LANES}, or dividing it with the head count a multiple "
                "of the quotient); use attn_impl='auto' (which picks XLA "
                "for such shapes) or 'xla'"
            )
        return blockwise_attention(q, k, v, causal=causal)
    return _flash(q, k, v, causal)
