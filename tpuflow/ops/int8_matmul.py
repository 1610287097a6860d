"""Fused native int8 matmul: the decode-path W8A8 contraction.

The failure this op exists to close (a chip record of 2026-07-31, since
deleted): weight-only int8 decode ran 0.76x vs fp at 124M/b8 because the
dequantize-into-matmul interceptor rebuilt bf16 weights per step —
convert + scale + write + read on top of the very matmul the int8 bytes
were supposed to shrink. The native path never materializes float
weights at all:

- activations are **dynamically quantized per row** (symmetric
  max-abs/127 over the contraction axis) at the matmul boundary;
- the contraction runs **int8 x int8 -> int32** — the form the MXU
  executes natively at 2x its bf16 rate on v5e, with integer (exact,
  width-independent) accumulation;
- the combined ``act_scale (x) weight_scale`` dequant is folded into the
  int32 -> float **epilogue**, one elementwise pass over the output.

Two implementations with BIT-IDENTICAL numerics (same rounding, same
clip, exact integer accumulation, same epilogue ops — pinned by
tests/test_int8_matmul.py):

- ``xla`` — ``lax.dot_general(int8, int8, preferred_element_type=int32)``
  plus an elementwise epilogue XLA fuses into the dot's output. Always
  available, every backend; XLA lowers the int8 dot to the MXU's native
  int8 path on TPU.
- ``pallas`` — one fused quantize-matmul-dequant kernel: the float
  activation tile quantizes to int8 *in VMEM* (the int8 copy never
  crosses HBM), the int32 accumulator lives in scratch across the K
  blocks, and the final K block applies the dequant epilogue before the
  single output write. This removes the quantize-op -> dot boundary XLA
  does not fuse across (the same fusion boundary that produced the
  0.76x dequant buffer, now on the activation side).

Dispatch mirrors ``tpuflow.ops.attention``'s flash thresholds:
``TPUFLOW_INT8_MATMUL`` forces ``xla`` | ``pallas`` (forced pallas runs
interpret-mode off-TPU, for tests); ``auto`` (default) picks pallas on
TPU when the shape tiles and the weight block is big enough for the
kernel to matter (``TPUFLOW_INT8_KERNEL_MIN_KN``, default K*N >= 2^18).
Untileable shapes — e.g. the 50257-column GPT-2 LM head — take the XLA
path under ``auto``, which is still native int8 end to end.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from tpuflow.utils import knobs

# Kernel worth it once the streamed weight block dominates the launch:
# K*N below this (e.g. tiny test models) stays on the XLA path under
# 'auto'. Env override TPUFLOW_INT8_KERNEL_MIN_KN, like the flash
# min-seq knobs.
_DEFAULT_KERNEL_MIN_KN = 1 << 18
# One M block per kernel launch (decode M = batch/slot count, small):
# bound it so the f32 activation tile + int32 accumulator stay well
# inside VMEM. Bigger-M callers (training-width scoring forwards) take
# the XLA path under 'auto'.
_KERNEL_MAX_M = 1024

_warned_env: set[str] = set()
_warned_fallback: set[tuple[int, int, int]] = set()

# Programmatic impl override for a whole trace region (stronger than the
# env var, weaker than an explicit per-call impl=): QuantizedModel
# threads its ``int8_impl`` field through here so every int8 matmul a
# wrapper's apply traces — Dense interceptions AND the LM head deep in
# the model — resolves the same way. Trace-time state: the choice bakes
# into the compiled program, and because the field rides the hashable
# static model arg, two wrappers with different impls get different jit
# cache keys (the property the fused-vs-interceptor numerics tests
# stand on).
_IMPL_OVERRIDE: list = [None]


@contextlib.contextmanager
def impl_override(impl: str | None):
    """Scope an implementation choice over every ``int8_matmul`` call
    traced inside the region; ``None`` is a no-op."""
    if impl is None:
        yield
        return
    prev = _IMPL_OVERRIDE[0]
    _IMPL_OVERRIDE[0] = impl
    try:
        yield
    finally:
        _IMPL_OVERRIDE[0] = prev


def row_scales(x, scale_dtype=jnp.float32):
    """Per-row symmetric quantization scale over the LAST axis:
    max-abs/127, all-zero rows pinned to 1/127 (quantize to 0 instead of
    dividing by zero). The ONE scale formula shared by the XLA path, the
    Pallas path, and the Flax interceptor (tpuflow.infer.quant) — the
    bit-exactness contract between them starts here."""
    amax = jnp.max(jnp.abs(x.astype(scale_dtype)), axis=-1, keepdims=True)
    return jnp.where(amax > 0.0, amax, 1.0) / 127.0


def quantize_rows(x, scale_dtype=jnp.float32):
    """Dynamic per-row symmetric int8 quantization over the last axis.
    Returns ``(q int8, scale)`` with ``x ~= q * scale``. Round half to
    even (jnp.round), clip to [-127, 127] (symmetric — no -128, so
    negation is lossless)."""
    scale = row_scales(x, scale_dtype)
    q = jnp.clip(
        jnp.round(x.astype(scale_dtype) / scale), -127, 127
    ).astype(jnp.int8)
    return q, scale


def kernel_min_kn() -> int:
    raw = knobs.raw("TPUFLOW_INT8_KERNEL_MIN_KN")
    if not raw:
        return _DEFAULT_KERNEL_MIN_KN
    try:
        return max(int(raw), 0)
    except ValueError:
        if raw not in _warned_env:
            _warned_env.add(raw)
            print(
                f"[tpuflow] malformed TPUFLOW_INT8_KERNEL_MIN_KN={raw!r} "
                f"(want an integer); using {_DEFAULT_KERNEL_MIN_KN}"
            )
        return _DEFAULT_KERNEL_MIN_KN


def _pick_block(dim: int, candidates=(512, 256, 128)) -> int | None:
    """Largest MXU-friendly block evenly dividing ``dim`` (lane dim must
    stay a multiple of 128 for int8 tiles); None when ``dim`` doesn't
    tile — the caller falls back to the XLA path."""
    for b in candidates:
        if dim % b == 0:
            return b
    return None


def kernel_supported(m: int, k: int, n: int) -> bool:
    """Whether the fused kernel can run this shape at all (tiling only —
    the 'auto' profitability thresholds live in resolve_int8_impl)."""
    return (
        m >= 1 and _pick_block(k) is not None and _pick_block(n) is not None
    )


def resolve_int8_impl(
    m: int, k: int, n: int, *, backend: str | None = None
) -> str:
    """Dispatch for one (M, K, N) int8 matmul — factored out of
    ``int8_matmul`` so the choice is unit-testable without a TPU (the
    ``resolve_attention_impl`` idiom). ``TPUFLOW_INT8_MATMUL`` forces
    ``xla``/``pallas``; ``auto`` picks the fused kernel on TPU when the
    shape tiles, M fits one VMEM-resident block, and the weight block
    clears ``TPUFLOW_INT8_KERNEL_MIN_KN``. Resolved at trace time —
    baked into the compiled program per shape, like the flash
    thresholds."""
    env = (
        knobs.raw("TPUFLOW_INT8_MATMUL", "auto").strip().lower()
        or "auto"
    )
    if env in ("xla", "pallas"):
        return env
    if env != "auto" and env not in _warned_env:
        _warned_env.add(env)
        print(
            f"[tpuflow] unknown TPUFLOW_INT8_MATMUL={env!r} "
            "(want auto|xla|pallas); using auto"
        )
    backend = backend if backend is not None else jax.default_backend()
    if backend != "tpu":
        return "xla"
    if not kernel_supported(m, k, n):
        return "xla"
    if m < 8 or m > _KERNEL_MAX_M:
        return "xla"
    if k * n < kernel_min_kn():
        return "xla"
    return "pallas"


# ----------------------------------------------------------- pallas kernel
def _int8_matmul_kernel(
    x_ref, xs_ref, w_ref, ws_ref, o_ref, acc_scr, *, w_contract_last: bool
):
    """Fused quantize-matmul-dequant over one (M, block_n) output tile,
    K blocks sequential (grid dim 1): the float activation tile
    quantizes to int8 in VMEM with the precomputed per-row scale, the
    int8 x int8 dot accumulates exactly in the int32 scratch, and the
    last K block folds ``act_scale * weight_scale`` into the single
    float output write."""
    kb = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kb == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    s = xs_ref[:, :1]  # per-row activation scale (lane-broadcast input)
    xq = jnp.clip(
        jnp.round(x_ref[:].astype(jnp.float32) / s), -127, 127
    ).astype(jnp.int8)
    dims = (
        (((1,), (1,)), ((), ()))
        if w_contract_last
        else (((1,), (0,)), ((), ()))
    )
    acc_scr[:] += jax.lax.dot_general(
        xq, w_ref[:], dims, preferred_element_type=jnp.int32
    )

    @pl.when(kb == nk - 1)
    def _final():
        o_ref[:] = (
            acc_scr[:].astype(jnp.float32) * s * ws_ref[:1, :]
        ).astype(o_ref.dtype)


def _pallas_int8_matmul(
    x2d, wq, w_scale_row, *, w_contract_last: bool, out_dtype, interpret: bool
):
    m, k = x2d.shape
    n = wq.shape[0] if w_contract_last else wq.shape[1]
    bk = _pick_block(k)
    bn = _pick_block(n)
    # Scale computed OUTSIDE the kernel (it needs the whole row, which
    # spans every K block) — cheap VPU work XLA fuses; the int8 values
    # themselves never leave VMEM. Broadcast layouts follow the flash
    # lse convention: row-shaped operands ride a full 128-lane minor
    # dim, channel-shaped ones an 8-row sublane dim, for TPU tiling.
    s = row_scales(x2d)
    xs = jnp.broadcast_to(s, (m, 128))
    ws = jnp.broadcast_to(
        w_scale_row.reshape(1, n).astype(jnp.float32), (8, n)
    )
    if w_contract_last:
        w_spec = pl.BlockSpec((bn, bk), lambda j, kb: (j, kb))
    else:
        w_spec = pl.BlockSpec((bk, bn), lambda j, kb: (kb, j))
    return pl.pallas_call(
        functools.partial(
            _int8_matmul_kernel, w_contract_last=w_contract_last
        ),
        grid=(n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, kb: (0, kb)),
            pl.BlockSpec((m, 128), lambda j, kb: (0, 0)),
            w_spec,
            pl.BlockSpec((8, bn), lambda j, kb: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, kb: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.int32)],
        # Output tiles are independent; the K loop is a sequential
        # reduction carrying the int32 accumulator in scratch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x2d, xs, wq, ws)


def _xla_int8_matmul(x2d, wq, w_scale_row, *, w_contract_last: bool,
                     out_dtype):
    """The always-available reference path: same quantization, same
    integer accumulation, same epilogue op order as the kernel — the two
    are bit-identical (integer adds are associative, the float epilogue
    is elementwise), pinned by tests/test_int8_matmul.py."""
    xq, s = quantize_rows(x2d)
    dims = (
        (((1,), (1,)), ((), ()))
        if w_contract_last
        else (((1,), (0,)), ((), ()))
    )
    acc = jax.lax.dot_general(
        xq, wq, dims, preferred_element_type=jnp.int32
    )
    out = (
        acc.astype(jnp.float32)
        * s
        * w_scale_row.reshape(1, -1).astype(jnp.float32)
    )
    return out.astype(out_dtype)


def int8_matmul(
    x,
    wq,
    w_scale,
    *,
    w_contract_last: bool = False,
    out_dtype=jnp.float32,
    impl: str | None = None,
):
    """``x (..., K) float @ wq int8 -> (..., N) out_dtype`` with dynamic
    per-row activation quantization and the dequant epilogue fused in.

    ``wq`` is ``(K, N)`` — a Dense kernel — or ``(N, K)`` with
    ``w_contract_last=True`` (the LM-head layout: GPT-2's tied ``wte``
    is ``(vocab, n_embd)``; contracting its LAST axis avoids ever
    materializing a transposed int8 copy). ``w_scale`` holds the
    per-out-channel weight scales, any shape of size N (or a single
    per-tensor scale). ``impl`` overrides the dispatch
    (``resolve_int8_impl``). A forced ``pallas`` on an untileable shape
    raises on the TPU backend — the kernel was asked for by name, and
    ``auto`` is the spelling that may pick XLA; off the TPU (interpret
    mode, tests) it takes the XLA path (numerics identical) with a
    ``quant.kernel_fallback`` event.
    """
    if wq.dtype != jnp.int8:
        raise TypeError(f"wq must be int8, got {wq.dtype}")
    if wq.ndim != 2:
        raise ValueError(f"wq must be 2-D, got shape {wq.shape}")
    k = x.shape[-1]
    n, kw = wq.shape if w_contract_last else wq.shape[::-1]
    if kw != k:
        raise ValueError(
            f"contraction mismatch: x (..., {k}) vs wq {wq.shape} "
            f"(w_contract_last={w_contract_last})"
        )
    w_scale = jnp.asarray(w_scale)
    if w_scale.size == 1:
        w_scale_row = jnp.broadcast_to(w_scale.reshape(()), (n,))
    elif w_scale.size == n:
        w_scale_row = w_scale.reshape(n)
    else:
        raise ValueError(
            f"w_scale has {w_scale.size} elements; want {n} "
            "(per-out-channel) or 1 (per-tensor)"
        )
    lead = x.shape[:-1]
    m = math.prod(lead) if lead else 1
    x2d = x.reshape(m, k)
    if impl in (None, "auto"):
        impl = _IMPL_OVERRIDE[0]
    chosen = impl if impl not in (None, "auto") else resolve_int8_impl(m, k, n)
    if chosen not in ("xla", "pallas"):
        raise ValueError(f"unknown int8 impl {chosen!r}; use xla|pallas")
    interpret = jax.default_backend() != "tpu"
    if chosen == "pallas" and not kernel_supported(m, k, n):
        shape = (m, k, n)
        if not interpret:
            raise ValueError(
                f"int8 pallas kernel cannot run (m, k, n)={shape}: K and N "
                "must be multiples of 128. TPUFLOW_INT8_MATMUL=auto picks "
                "XLA for such shapes (GPT-2's 50257-column head is one)."
            )
        if shape not in _warned_fallback:
            _warned_fallback.add(shape)
            from tpuflow import obs

            obs.event(
                "quant.kernel_fallback", m=m, k=k, n=n,
                reason="shape does not tile (K/N % 128)",
            )
        chosen = "xla"
    if chosen == "pallas":
        out = _pallas_int8_matmul(
            x2d, wq, w_scale_row,
            w_contract_last=w_contract_last, out_dtype=out_dtype,
            interpret=interpret,
        )
    else:
        out = _xla_int8_matmul(
            x2d, wq, w_scale_row,
            w_contract_last=w_contract_last, out_dtype=out_dtype,
        )
    return out.reshape(*lead, n)
