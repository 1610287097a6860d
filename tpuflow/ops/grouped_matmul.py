"""Grouped matrix products: rows sorted by group, one matrix a group.

What a routed-expert layer needs (``models/xing4.py``): ``lhs`` (M, K), its
rows sorted by group, times ``rhs`` (G, K, N) group by group, ``sizes``
(G,) rows each. A group of no rows is not visited, so its matrix is never
read: a decode step reads the experts its live rows chose and no other.

The whole choice of implementation is ``resolve_grouped_impl`` (the
``resolve_attention_impl`` idiom): on a TPU the Pallas grouped matrix
product that ships with jax (``megablox.gmm``), elsewhere
``lax.ragged_dot``. Why not ``lax.ragged_dot`` on the chip: XLA's TPU
lowering of it is a custom call that wants its matrices contiguous, so a
scanned layer's slice of stacked experts was copied whole at every step,
and the call carries no named scope (PERF.md section 6, PR 34, chip call
1); the Pallas kernel takes the stacked leaf as it lies and keeps the
scope.

Tiles of the Pallas kernel (``gmm_tiling``) follow from the shapes; they
were not swept. The kernel visits a group once for each row tile that
holds some of its rows, reads the group's matrix whole at each visit and
multiplies the whole tile by it, whatever rows of the tile are the
group's. What was read on the chip at Xing4.0's expert shapes, (3584 ->
2048) and (1024 -> 3584) in bfloat16, with all of a step's (token,
expert) pairs in one tile (PERF.md section 6, PR 34, the ``serve.decode``
spans of calls 5, 9 and 11): 26.2 us a visit of both products with 32
rows a tile, 32.5 with 64, 47.2 with 128, where the read alone is 26.9 us
at the memory peak. So a decode step, whose groups hold two to four rows
each, wants small tiles, and a prefill, whose groups hold a hundred,
large ones. With tiles of 32 rows at 16, 24 and 32 rows live the visits
themselves were not timed (call 12 ran no profile): end to end the
cell's tail at 2.1 requests a second, which lives at 16 rows, read no
lower than with a tile of 64.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# A block of a group's matrix: at most this many bytes, so that two of them
# (the pipeline holds the next beside the current) stay well inside the
# 16 MiB of fast memory a v5e program gets, and at most _MAX_TILE_K deep.
_BLOCK_BYTES = 4 << 20
_MAX_TILE_K = 2048
_MAX_TILE_M = 256
_MIN_TILE_M = 32  # the smallest read on the chip (a decode block of 8 rows)
# Rows a tile must hold for a visit to multiply as long as it reads, with
# bfloat16 matrices on a v5e: 197 TFLOP/s over 819 GB/s, one byte of
# matrix to one row's product with it. In powers of two.
_RIDGE_ROWS = 256


def resolve_grouped_impl(impl: str = "auto", *, backend: str | None = None) -> str:
    """``'gmm'`` (the Pallas kernel) or ``'ragged_dot'`` (XLA's). A named
    ``impl`` passes through; ``'auto'`` is the kernel on a TPU and XLA's
    elsewhere. Resolved at trace time."""
    if impl != "auto":
        return impl
    backend = backend if backend is not None else jax.default_backend()
    return "gmm" if backend == "tpu" else "ragged_dot"


def _tile(size: int, most: int) -> int:
    """The largest multiple of 128 that divides ``size`` and is at most
    ``most``; ``size`` itself where none does."""
    fits = [t for t in range(128, most + 1, 128) if size % t == 0]
    return max(fits) if fits else size


def gmm_tiling(
    m: int, k: int, n: int, itemsize: int, groups: int
) -> tuple[int, int, int]:
    """(rows, depth, width) of the kernel's blocks for an (M, K) x (G, K, N)
    product whose rows fall into ``groups`` groups. Rows: with tiles of t
    rows the kernel makes about ``groups + m / t`` visits (a tile's edge
    splits one group), each a read of a matrix and a product of t rows
    with it, which costs as much as the read at ``_RIDGE_ROWS``; the sum is
    least at t = sqrt(``_RIDGE_ROWS`` x m / groups), taken up to the next
    power of two between ``_MIN_TILE_M`` and ``_MAX_TILE_M``, and never more
    than the rows there are (in eights). Then the deepest divisor of K up
    to ``_MAX_TILE_K`` and the widest divisor of N that keeps a block of
    the matrix within ``_BLOCK_BYTES``: long blocks, so a grid step's fixed
    cost is hidden behind its read."""
    best = math.sqrt(_RIDGE_ROWS * m / groups)
    tm = min(max(1 << math.ceil(math.log2(best)), _MIN_TILE_M), _MAX_TILE_M)
    tm = min(tm, -(-m // 8) * 8)
    tk = _tile(k, _MAX_TILE_K)
    tn = _tile(n, max(_BLOCK_BYTES // (itemsize * tk), 128))
    return tm, tk, tn


def grouped_dot(
    lhs, rhs, sizes, *, groups: int | None = None, impl: str = "auto",
    interpret: bool = False,
):
    """``lhs`` (M, K), its rows sorted by group, times ``rhs`` (G, K, N)
    group by group, ``sizes`` (G,) rows each; float32 out. Rows past the
    last group hold nothing defined. A group of no rows is not visited:
    its matrix is not read. ``groups`` says how many of the G groups may
    hold rows where the caller knows the others empty (one layer's experts
    of a leaf that stacks every layer's); the row tile follows from it.
    ``interpret`` runs the Pallas kernel in interpret mode (tests, off the
    chip)."""
    impl = resolve_grouped_impl(impl)
    if impl == "ragged_dot":
        return lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=jnp.float32)
    if impl != "gmm":
        raise ValueError(f"unknown grouped product {impl!r} (want auto|gmm|ragged_dot)")
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = lhs.shape
    tiling = gmm_tiling(
        m, k, rhs.shape[-1], rhs.dtype.itemsize, groups or rhs.shape[0]
    )
    pad = -m % tiling[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(
        lhs, rhs, sizes, preferred_element_type=jnp.float32, tiling=tiling,
        interpret=interpret,
    )
    return out[:m] if pad else out
