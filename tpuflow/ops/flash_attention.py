"""Blockwise / flash attention: O(T) memory attention for TPU.

Two tiers with identical numerics:

- ``blockwise_attention`` — pure-JAX online-softmax attention via ``lax.scan``
  over KV chunks. O(block) memory instead of O(T^2), differentiable, runs on
  any backend; the building block of ring attention.
- ``flash_attention`` — Pallas TPU kernels (MXU matmuls in the q/k blocks,
  float32 online-softmax state in VMEM scratch). Forward saves only
  (O, logsumexp); backward recomputes P inside two FUSED Pallas kernels
  (dq + row-delta; dk/dv merged) — the flash-style compute-for-memory
  trade. ``TPUFLOW_FLASH_BWD`` selects the backward: ``fused`` (default;
  the ISSUE 10 two-kernel design), ``split`` (the previous per-visit
  row-delta kernels, kept one release as the on-chip regression
  reference), ``blockwise`` (the pure-JAX recompute VJP).

The reference has no attention anywhere (its model is an image MLP,
my_ray_module.py:94-112); these exist for the GPT-2 acceptance config and
first-class long-context support (SURVEY.md §5).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from tpuflow.utils import knobs

_NEG_INF = -1e30


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


# ----------------------------------------------------------- pallas kernel
def _masked_scores(q_ref, k_ref, iq, ik, *, scale, causal, block_q, block_k):
    """Scaled (block_q, block_k) f32 score tile with the causal mask applied.

    Shared by the forward and both backward kernels so the mask/scale
    semantics cannot diverge between them. MXU feeds stay in the input dtype
    (bf16 multiplies at full MXU rate); accumulation is f32 via
    preferred_element_type.
    """
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    return s


def _fwd_kernel_nolse(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                      **kw):
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref, None, m_scr, l_scr, acc_scr, **kw)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        # Only the softmax statistics run in f32 on the VPU.
        s = _masked_scores(
            q_ref, k_ref, iq, ik,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        m_old = m_scr[:, 0]
        m_new = jnp.maximum(m_old, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_old - m_new)
        l_new = l_scr[:, 0] * corr + p.sum(axis=-1)
        acc_scr[:] = acc_scr[:] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, 0] = m_new
        l_scr[:, 0] = l_new

    if causal:
        # Causal block skip: a KV block strictly above the diagonal is fully
        # masked — skip its compute entirely (~2x fewer FLOPs at long T).
        @pl.when(ik * block_k <= iq * block_q + block_q - 1)
        def _maybe():
            _compute()
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _final():
        o_ref[0] = (
            acc_scr[:] / jnp.maximum(l_scr[:, 0], 1e-30)[:, None]
        ).astype(o_ref.dtype)
        if lse_ref is not None:
            # Mosaic requires ≥(8,128)-tileable outputs: lse rides a full
            # 128-lane minor dim (value broadcast across lanes), the same
            # layout the reference TPU flash kernels use for their softmax
            # residuals. Only the VJP forward emits it — the primal path
            # skips the output entirely (pallas outputs are opaque to XLA
            # DCE, so an unused lse would still be written to HBM).
            lse = m_scr[:, 0] + jnp.log(jnp.maximum(l_scr[:, 0], 1e-30))
            lse_ref[0] = jax.lax.broadcast_in_dim(
                lse, lse_ref.shape[1:], (0,)
            )


def _flash_fwd(q, k, v, causal: bool, block_q: int, block_k: int,
               interpret: bool, *, with_lse: bool = False):
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Tk, D)
    grid = (B * H, Tq // block_q, Tk // block_k)
    o_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    o_shape = jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype)
    if with_lse:
        kernel = functools.partial(
            _fwd_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        )
        out_specs = [
            o_spec,
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ]
        out_shape = [
            o_shape,
            # logsumexp per row — the softmax residual the backward kernels
            # need to recompute P without re-running the online softmax.
            # Broadcast over a 128-lane minor dim for TPU tiling.
            jax.ShapeDtypeStruct((B * H, Tq, 128), jnp.float32),
        ]
    else:
        # Primal/inference path: no lse output at all — pallas outputs are
        # written unconditionally, so emitting-then-dropping it would cost
        # a full (BH, Tq, 128) f32 HBM write per call.
        kernel = functools.partial(
            _fwd_kernel_nolse, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        )
        out_specs = o_spec
        out_shape = o_shape
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max (col 0)
            pltpu.VMEM((block_q, 128), jnp.float32),  # running denom (col 0)
            pltpu.VMEM((block_q, D), jnp.float32),  # output accumulator
        ],
        # batch·head and q-block programs are independent; the k loop is a
        # sequential reduction (carries the softmax state in scratch).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf)
    out, lse = res if with_lse else (res, None)
    out = out.reshape(B, H, Tq, D).transpose(0, 2, 1, 3)
    if with_lse:
        return out, lse
    return out


# -------------------------------------------------- pallas backward kernels
# FlashAttention-2-style backward: P is recomputed inside the kernels from
# (q, k, lse) — the compute-for-memory trade — in two kernels so each
# accumulates over its own sequential axis without atomics:
#   dq kernel : grid (BH, nq, nk), k innermost — dq_i += dS_ij K_j
#   dkv kernel: grid (BH, nk, nq), q innermost — dK_j += dS_ij^T Q_i,
#                                                dV_j += P_ij^T dO_i
# with dS = P ∘ (dP − D), dP = dO V^T, D = rowsum(dO ∘ O).
#
# Two shapes of the pair exist (ISSUE 10):
#
# - FUSED (default): the dq kernel computes D once per q block at its
#   FIRST kv-block visit (f32 scratch, not per visit) and packs the two
#   per-row softmax residuals into ONE lane-addressed (BH, Tq, 128) f32
#   tensor — lane 0 = lse (bit-copied from the forward residual), lane 1
#   = D. The merged dk/dv kernel then reads that single residual instead
#   of (lse + o): its q-innermost walk re-streams each q row's operands
#   nk times, so dropping the o stream and the per-visit rowsum removes
#   one full HBM pass and nk-1 VPU reduces per row — the short-T regime
#   where a v5e record of 2026-07-31 had the backward losing 5x to XLA is exactly
#   where that per-visit residual traffic rivals the useful q/k/v bytes.
# - SPLIT (TPUFLOW_FLASH_BWD=split, one release as the regression
#   reference): the previous kernels — D recomputed from (o, do) inside
#   EVERY block visit of both kernels.
#
# The two are bit-identical by construction (same op order; D is the
# same f32 value whether recomputed or round-tripped through f32 HBM) —
# pinned in interpret mode by tests/test_attention.py, and raced on chip
# by the bench flash leg's fused-vs-split column.


def _row_delta(o_ref, do_ref):
    """D_i = rowsum(dO ∘ O) for the current q block → (block_q, 1) f32."""
    return jnp.sum(
        do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
        axis=-1, keepdims=True,
    )


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        s = _masked_scores(
            q_ref, k_ref, iq, ik,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - _row_delta(o_ref, do_ref)) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(ik * block_k <= iq * block_q + block_q - 1)
        def _maybe():
            _compute()
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _final():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dk_ref,
                    dv_ref, dk_scr, dv_scr, *, scale, causal, block_q,
                    block_k):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        s = _masked_scores(
            q_ref, k_ref, iq, ik,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        p = jnp.exp(s - lse_ref[0][:, :1])  # (block_q, block_k)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - _row_delta(o_ref, do_ref)) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # A q block entirely before this k block contributes nothing.
        @pl.when(iq * block_q + block_q - 1 >= ik * block_k)
        def _maybe():
            _compute()
    else:
        _compute()

    @pl.when(iq == nq - 1)
    def _final():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


# Lane indices of the packed per-row residual tensor the fused backward
# kernels share: lane 0 carries lse (bit-copied from the forward
# residual), lane 1 carries D = rowsum(dO ∘ O). The kernels only ever
# read one column of a lane-broadcast residual anyway, so the 128-lane
# minor dim Mosaic requires is free real estate — packing both residuals
# into one tensor halves the dkv kernel's residual streams.
_RES_LSE_LANE = 0
_RES_DELTA_LANE = 1


def _bwd_dq_fused_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                         dq_ref, res_ref, dq_scr, delta_scr, *, scale,
                         causal, block_q, block_k):
    """dq + row-delta in one pass (ISSUE 10 fused design).

    Identical math to ``_bwd_dq_kernel`` except D is computed ONCE per q
    block — at the first kv-block visit, into f32 scratch — instead of
    per visit, and the (lse, D) pair is written out as the lane-packed
    residual the fused dkv kernel consumes. o/do/lse block fetches are
    hoisted by Mosaic (their index maps ignore the kv grid axis), so the
    saving here is the nk-1 redundant VPU reduces; the HBM saving lands
    in the dkv kernel, which stops streaming o entirely.
    """
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # D_i once per q block. Under the causal block skip ik == 0 is
        # never skipped (the diagonal block's kv start is 0), so the
        # scratch and the residual are always populated.
        delta = _row_delta(o_ref, do_ref)  # (block_q, 1) f32
        delta_scr[:] = jax.lax.broadcast_in_dim(
            delta[:, 0], delta_scr.shape, (0,)
        )
        lane = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, res_ref.shape[-1]), 1
        )
        # Lane 0 keeps the forward's lse bits exactly (bit-parity with
        # the split kernels, which read lse straight from the forward).
        res_ref[0] = jnp.where(
            lane == _RES_DELTA_LANE, delta_scr[:], lse_ref[0]
        )

    def _compute():
        s = _masked_scores(
            q_ref, k_ref, iq, ik,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_scr[:, :1]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(ik * block_k <= iq * block_q + block_q - 1)
        def _maybe():
            _compute()
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _final():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_fused_kernel(q_ref, k_ref, v_ref, do_ref, res_ref, dk_ref,
                          dv_ref, dk_scr, dv_scr, *, scale, causal,
                          block_q, block_k):
    """Merged dk/dv over one KV-grid walk, consuming the packed residual.

    vs ``_bwd_dkv_kernel``: o is not an input and D is not recomputed —
    lse and D both come out of the single lane-packed residual the fused
    dq kernel wrote. The q-innermost walk re-streams every q-indexed
    operand nk times, so this drops one full (BH, Tq, D) HBM stream per
    outer kv block plus the per-visit rowsum.
    """
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        s = _masked_scores(
            q_ref, k_ref, iq, ik,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        lse = res_ref[0][:, _RES_LSE_LANE:_RES_LSE_LANE + 1]
        p = jnp.exp(s - lse)  # (block_q, block_k)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        delta = res_ref[0][:, _RES_DELTA_LANE:_RES_DELTA_LANE + 1]
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # A q block entirely before this k block contributes nothing.
        @pl.when(iq * block_q + block_q - 1 >= ik * block_k)
        def _maybe():
            _compute()
    else:
        _compute()

    @pl.when(iq == nq - 1)
    def _final():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_fused(q, k, v, o, lse, g, causal, block_q, block_k,
                     interpret):
    """The fused two-kernel backward (default; see the section comment)."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    BH = B * H

    def flat(x, T):
        return x.transpose(0, 2, 1, 3).reshape(BH, T, D)

    qf, kf, vf = flat(q, Tq), flat(k, Tk), flat(v, Tk)
    of, gf = flat(o, Tq), flat(g, Tq)
    if lse.ndim == 2:  # TPUFLOW_FLASH_LSE=compact residual — reinflate
        lse = jnp.broadcast_to(lse[..., None], (*lse.shape, 128))

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    lse_spec = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))
    dq, res = pl.pallas_call(
        functools.partial(
            _bwd_dq_fused_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=(BH, Tq // block_q, Tk // block_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            q_spec,
            q_spec,
            lse_spec,
        ],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
            # The packed (lse, D) residual for the dkv kernel.
            jax.ShapeDtypeStruct((BH, Tq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf, of, gf, lse)

    k_spec = pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))
    qi_spec = pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0))
    resi_spec = pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_fused_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=(BH, Tk // block_k, Tq // block_q),
        in_specs=[qi_spec, k_spec, k_spec, qi_spec, resi_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf, gf, res)

    def unflat(x, T):
        return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)

    return unflat(dq, Tq), unflat(dk, Tk), unflat(dv, Tk)


def _flash_bwd_split(q, k, v, o, lse, g, causal, block_q, block_k,
                     interpret):
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = 1.0 / (D ** 0.5)
    BH = B * H

    def flat(x, T):
        return x.transpose(0, 2, 1, 3).reshape(BH, T, D)

    qf, kf, vf = flat(q, Tq), flat(k, Tk), flat(v, Tk)
    of, gf = flat(o, Tq), flat(g, Tq)
    # lse normally arrives in the kernels' native (BH, Tq, 128)
    # lane-broadcast layout straight from the forward — no
    # slice/rebroadcast round trip (at short T those two extra HBM
    # passes rival the useful q/k/v traffic). Under
    # TPUFLOW_FLASH_LSE=compact the residual is (BH, Tq) and is
    # reinflated here.
    if lse.ndim == 2:
        lse = jnp.broadcast_to(lse[..., None], (*lse.shape, 128))

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    lse_spec = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=(BH, Tq // block_q, Tk // block_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            q_spec,
            q_spec,
            lse_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf, of, gf, lse)

    k_spec = pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))
    qi_spec = pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0))
    lsei_spec = pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k,
        ),
        grid=(BH, Tk // block_k, Tq // block_q),
        in_specs=[qi_spec, k_spec, k_spec, qi_spec, qi_spec, lsei_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Tk, D), k.dtype),
            jax.ShapeDtypeStruct((BH, Tk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf, of, gf, lse)

    def unflat(x, T):
        return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)

    return unflat(dq, Tq), unflat(dk, Tk), unflat(dv, Tk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_k):
    return _flash_fwd(q, k, v, causal, block_q, block_k, _interpret())


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k):
    o, lse = _flash_fwd(
        q, k, v, causal, block_q, block_k, _interpret(), with_lse=True
    )
    # The residual keeps the kernel's native (BH, Tq, 128) lane-broadcast
    # layout by default (the same choice as the reference TPU flash
    # kernels, which hold their l/m residuals this way): slicing to a
    # compact (BH, Tq) here and re-broadcasting in the backward costs two
    # full-array HBM passes per step, which at short T dominates the
    # backward. The 128x f32 residual is transient per layer under remat;
    # WITHOUT remat it is held for every layer simultaneously and roughly
    # doubles attention's residual bytes — TPUFLOW_FLASH_LSE=compact
    # restores the small residual for memory-bound remat-off configs
    # (trading the two HBM passes back).
    if knobs.raw("TPUFLOW_FLASH_LSE") == "compact":
        return o, (q, k, v, o, lse[..., 0])
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, block_q, block_k, res, g):
    q, k, v, o, lse = res
    mode = knobs.raw("TPUFLOW_FLASH_BWD", "fused")
    if mode == "blockwise":
        # Fallback: recompute through the O(T)-memory blockwise path.
        _, vjp = jax.vjp(
            lambda q, k, v: blockwise_attention(q, k, v, causal=causal),
            q, k, v,
        )
        return vjp(g)
    interpret = _interpret()
    if mode == "split":
        # The pre-ISSUE-10 two-pass kernels, kept one release as the
        # on-chip regression reference (the bench flash leg races them
        # against the fused pair and fails on a fused loss at T2048).
        return _flash_bwd_split(
            q, k, v, o, lse, g, causal, block_q, block_k, interpret
        )
    # Trace-time marker: which compiled programs took the fused backward
    # (each jit trace of a differentiated flash call lands here once).
    from tpuflow import obs

    obs.event(
        "ops.flash_bwd_fused", seq=int(q.shape[1]), heads=int(q.shape[2]),
        causal=bool(causal), block_q=block_q, block_k=block_k,
    )
    return _flash_bwd_fused(
        q, k, v, o, lse, g, causal, block_q, block_k, interpret
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_tiles(
    tq: int, tk: int, d: int, block_q: int = 256, block_k: int = 256
) -> bool:
    """Whether the kernels can run this shape: each sequence length a
    multiple of its (length-capped) block, head_dim a multiple of 8."""
    return not (tq % min(block_q, tq) or tk % min(block_k, tk) or d % 8)


def flash_attention(
    q, k, v, *, causal: bool = True, block_q: int = 256, block_k: int = 256
):
    """Pallas TPU flash attention. q,k,v: (B,T,H,D) → (B,T,H,D).

    A shape that does not tile (``flash_tiles``) raises on the TPU
    backend: the kernel was asked for by name, and ``impl='auto'`` is the
    spelling that may pick XLA. Off the TPU — interpret mode, tests —
    such a shape takes ``blockwise_attention``, the same math.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if not flash_tiles(Tq, Tk, D, block_q, block_k):
        if not _interpret():
            raise ValueError(
                f"flash attention cannot run q{tuple(q.shape)} "
                f"k{tuple(k.shape)}: sequence lengths must be multiples "
                f"of the {block_q}x{block_k} blocks and head_dim of 8; "
                "use attn_impl='auto' (which picks XLA for such shapes) "
                "or 'xla'"
            )
        return blockwise_attention(q, k, v, causal=causal)
    out = _flash(
        q, k, v, causal, min(block_q, Tq), min(block_k, Tk)
    )
    from jax.ad_checkpoint import checkpoint_name

    # Named for selective-remat policies (ISSUE 10): the 'dots' policy
    # saves this output alongside the MXU dot outputs. The lse softmax
    # residual lives INSIDE the custom_vjp, which jax's remat treats
    # atomically — a remat'd block re-runs the flash forward for it
    # regardless of policy (measured: one extra fwd pallas_call in the
    # remat'd backward jaxpr). Truly saving "flash outputs + lse"
    # therefore means NOT remat'ing — the TPUFLOW_REMAT_POLICY=none mode,
    # where the vjp residuals (q, k, v, o, lse) are held from the forward
    # and the backward runs zero recompute.
    return checkpoint_name(out, "flash_out")
