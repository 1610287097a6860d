"""The decode step of the gated delta rule in place
(``tpuflow/ops/gated_delta.py`` ``step_in_place``): one Pallas kernel that
advances each row's S where it lies in a serving engine's leaf (layers,
slots + a trash row, heads, dk, dv), run here through Pallas's
interpreter, against the path it replaces: the rows gathered by slot,
``step``, and scattered back.

At both models' heads (Qwen3-Next's 32 x 128 x 128, Olmo-Hybrid's 30 x 96
x 192) and write strengths up to 1 and up to 2, on states the rule itself
made: ``o`` and the live rows' new S agree to 1e-6 (the two differ by the
order of their float32 sums alone), and every slot the call does not name,
and every other layer, is the same bit for bit. The rows mix live slots
with dead rows (the trash row) at the start, in the middle and at the end;
the first visit to the trash row alone computes, the other dead rows read
``o`` = 0.

The short causal conv before the rule keeps its state beside S, and
``conv_step_in_place`` advances it where it lies in the same way: at both
models' channels, its output agrees with the window gathered by slot to
1e-6, the live rows' state is the gathered path's bit for bit, and every
other row of the leaf, the neighbours in a live row's tile of 8 among them,
is as it was.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tpuflow.ops import gated_delta  # noqa: E402

TOL = 1e-6
LAYERS, SLOTS = 3, 6  # and the trash row, the leaf's last


def _unit(x):
    return x / np.sqrt((x * x).sum(-1, keepdims=True))


def _inputs(rng, shape, h, dk, dv, beta_max):
    """Inputs of the rule as the model makes them (q, k unit length, q
    scaled; g < 0; beta in (0, ``beta_max``)), of ``shape`` + (h, ...)."""
    q = _unit(rng.standard_normal(shape + (h, dk))) * dk ** -0.5
    k = _unit(rng.standard_normal(shape + (h, dk)))
    v = rng.standard_normal(shape + (h, dv))
    g = -np.exp(rng.uniform(-7.0, 1.0, shape + (h,)))
    beta = rng.uniform(0.0, beta_max, shape + (h,))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta)]


@pytest.mark.parametrize("beta_max", [1.0, 2.0], ids=["beta1", "beta2"])
@pytest.mark.parametrize("h,dk,dv", [(32, 128, 128), (30, 96, 192)],
                         ids=["qwen3_next", "olmo_hybrid"])
def test_the_step_in_place_equals_gather_step_scatter(h, dk, dv, beta_max):
    rng = np.random.default_rng(h + int(beta_max))
    rows = LAYERS * (SLOTS + 1)
    # Every row's S after 12 tokens of the rule from nought.
    _, made = jax.jit(gated_delta.recurrent)(
        *_inputs(rng, (rows, 12), h, dk, dv, beta_max),
        jnp.zeros((rows, h, dk, dv), jnp.float32),
    )
    leaf = made.reshape(LAYERS, SLOTS + 1, h, dk, dv)
    trash = SLOTS
    slots = jnp.asarray([trash, 4, 1, trash, 5, trash], jnp.int32)
    layer = 1
    q, k, v, g, beta = _inputs(rng, (slots.shape[0],), h, dk, dv, beta_max)

    o, new = jax.jit(gated_delta.step_in_place)(leaf, layer, slots, q, k, v, g, beta)
    want_o, want_s = gated_delta.step(q, k, v, g, beta, leaf[layer, slots])

    live = np.asarray(slots) != trash
    named = np.asarray(slots)[live]
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live], atol=TOL, rtol=0)
    np.testing.assert_allclose(
        np.asarray(new)[layer, named], np.asarray(want_s)[live], atol=TOL, rtol=0
    )
    assert np.abs(np.asarray(new)[layer, named] - np.asarray(leaf)[layer, named]).max() > 1e-3
    # Of the dead rows, the first visit to the trash row computes and the
    # others read nought.
    dead = np.asarray(o)[~live]
    assert np.isfinite(dead).all() and sum(bool(d.any()) for d in dead) == 1
    untouched = np.ones((LAYERS, SLOTS + 1), bool)
    untouched[layer, np.asarray(slots)] = False
    np.testing.assert_array_equal(np.asarray(new)[untouched], np.asarray(leaf)[untouched])


@pytest.mark.parametrize("h,dk,dv,heads", [
    (32, 128, 128, 32), (30, 96, 192, 15), (64, 128, 128, 32), (7, 8, 64, 7),
])
def test_a_visit_takes_the_most_heads_that_fit_its_block(h, dk, dv, heads):
    """The heads of one visit divide the row's and fill at most
    ``BLOCK_BYTES`` of VMEM as the chip tiles it (8 rows, 128 lanes: 192
    lanes take 256): the block in and out, double-buffered, then hold half
    of the compiler's default 16 MiB."""
    got = gated_delta._head_block(h, dk, dv)
    assert got == heads and h % got == 0
    assert got * dk * -(-dv // 128) * 128 * 4 <= gated_delta.BLOCK_BYTES


@pytest.mark.parametrize("channels", [8192, 11520], ids=["qwen3_next", "olmo_hybrid"])
def test_the_conv_step_in_place_equals_gather_conv_scatter(channels):
    slots_n = 12  # and the trash row: two tiles of 8, the second partial
    taps = 3
    rng = np.random.default_rng(channels)
    leaf = jnp.asarray(rng.standard_normal((LAYERS, slots_n + 1, taps * channels)), jnp.float32)
    trash = slots_n
    # Live slots in both tiles, two of them in one; dead rows among them.
    slots = jnp.asarray([trash, 9, 1, trash, 3, 12 - 1, trash], jnp.int32)
    b, layer = slots.shape[0], 2
    x = jnp.asarray(rng.standard_normal((b, channels)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((taps + 1, channels)), jnp.float32)

    out, new = jax.jit(gated_delta.conv_step_in_place)(leaf, layer, slots, x, w)
    window = jnp.concatenate([leaf[layer, slots].reshape(b, taps, channels), x[:, None]], axis=1)
    want = sum(w[j] * window[:, j] for j in range(taps + 1))

    live = np.asarray(slots) != trash
    named = np.asarray(slots)[live]
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(want)[live], atol=TOL, rtol=0)
    np.testing.assert_array_equal(
        np.asarray(new)[layer, named], np.asarray(window[:, 1:].reshape(b, -1))[live]
    )
    assert np.isfinite(np.asarray(out)).all()
    untouched = np.ones((LAYERS, slots_n + 1), bool)
    untouched[layer, np.asarray(slots)] = False
    np.testing.assert_array_equal(np.asarray(new)[untouched], np.asarray(leaf)[untouched])
