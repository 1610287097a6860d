"""The serving engine's page pool as a model's paged decode path holds it.

A pool leaf is ``(..., pages, page_size, width)``: what a token holds
(K or V of (H, D), a latent vector), flattened to ONE vector and
zero-padded to ``token_width`` numbers. The chip lays a leaf out
page-major, the order every program indexes it in, only when its minor
axis fills whole 128-lane rows; a leaf whose minor axis does not
(``(..., 16, 64)``, ``(..., 576)``) gets the page axis moved to the lanes,
and every decode block and every insert then copies the whole pool to the
other layout and back (ISSUE 35; ``tests/test_paged_pool_inplace.py``
compiles both families' programs for a described v5e and reads the
layout). The rule is a function of the shape alone: no layout is asked
for, so nothing a compile cache can lose.

The pad lanes are written as zeros and sliced off the gathered rows
before any product (``read_rows``), so what they hold never reaches a
logit.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

LANES = 128  # a vector register's minor extent, and the tile's, on a TPU


def token_width(held: int) -> int:
    """The pool's minor axis for a token that holds ``held`` numbers:
    ``held`` rounded up to whole 128-lane rows."""
    return -(-int(held) // LANES) * LANES


def token_slots(page_table, slot_index, t: int, page_size: int, first_page):
    """Where row b's ``t`` new tokens land. ``page_table`` (B, W) int32,
    ``slot_index`` (B,) the logical column of each row's first new token,
    ``first_page`` the layer's first page in the pool flattened over
    (layer, page). Returns ``(pos, flat)``, both (B, t): the logical
    columns, and the rows of the pool flattened to ``(layers * pages *
    page_size, width)``. Columns beyond the table and dead rows (tables
    zeroed by the engine) route to the layer's page 0, the trash page
    nothing reads."""
    pos = slot_index[:, None] + jnp.arange(t)[None, :]
    page = jnp.take_along_axis(
        page_table,
        jnp.clip(pos // page_size, 0, page_table.shape[1] - 1),
        axis=1,
    )
    width = page_table.shape[1] * page_size
    flat = first_page * page_size + jnp.where(
        pos < width, page * page_size + pos % page_size, 0
    )
    return pos, flat


def pad_lanes(rows, width: int):
    """``rows`` (..., held), a token's numbers as one vector, with zero
    lanes appended up to ``width``."""
    held = rows.shape[-1]
    if held == width:
        return rows
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, width - held)])


def strip_lanes(rows, token_shape):
    """The inverse, on rows read out of the pool (a jax or a numpy
    array): ``(..., width)`` less its pad lanes, as ``(..., *token)``."""
    held = math.prod(token_shape)
    if held < rows.shape[-1]:
        rows = rows[..., :held]
    return rows.reshape(rows.shape[:-1] + tuple(token_shape))


def write_tokens(pool, flat, new):
    """Scatter ``new`` (B, t, *token) into ``pool`` (..., pages,
    page_size, width) at the flattened rows ``flat`` (B, t): one scatter
    of B * t rows, each a token's numbers followed by zero lanes up to
    ``width``."""
    width = pool.shape[-1]
    rows = pad_lanes(new.astype(pool.dtype).reshape(flat.size, -1), width)
    return pool.reshape(-1, width).at[flat.reshape(-1)].set(rows).reshape(
        pool.shape
    )


def read_rows(pool, pages, token_shape):
    """Each row's logical view through its table: ``pages`` (B, W) are
    page numbers in the pool flattened over (layer, page); returns (B, W *
    page_size, *token_shape). One gather of B x W pages; the pad lanes
    are sliced off the gathered rows (never off the pool)."""
    page_size, width = pool.shape[-2:]
    b, w = pages.shape
    rows = pool.reshape(-1, page_size, width)[pages]  # (B, W, ps, width)
    return strip_lanes(rows.reshape(b, w * page_size, width), token_shape)
