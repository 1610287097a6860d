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
does below 1 (``tests/test_olmo_hybrid.py``). Two forms of it, one
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

Both keep ``S`` and their arithmetic in float32 at the highest matmul
precision. A token with ``g = 0`` and ``β = 0`` (and ``q``, ``k``, ``v``
zero, a left pad) leaves ``S`` as it was, which is what makes a left-padded
prompt's final state that of the prompt alone. The caller normalises ``q``
and ``k``, scales ``q`` and brings both to the value heads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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
