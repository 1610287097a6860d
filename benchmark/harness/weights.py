"""Weights from `--seed`, made on the device in one jitted call.

The benchmark makes the weights, in float32 and in the layout the program's
GPT-2 declares under `scan_layers` (per-layer leaves stacked on a leading
layer axis), and hands the same values to the program and to the plain
reference. Neither takes anything the other has made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def leaf_table(m: dict) -> list[tuple[tuple[str, ...], tuple[int, ...], float, float]]:
    """(path, shape, mean, std) of every leaf, in a fixed order."""
    c, v, t, l = m["n_embd"], m["vocab_size"], m["n_ctx"], m["n_layer"]
    proj_std = 0.02 / (2.0 * l) ** 0.5  # GPT-2's scaled residual projections
    blk = ("h", "block")
    return [
        (("wte",), (v, c), 0.0, 0.02),
        (("wpe",), (t, c), 0.0, 0.01),
        (blk + ("ln_1", "scale"), (l, c), 1.0, 0.02),
        (blk + ("ln_1", "bias"), (l, c), 0.0, 0.01),
        (blk + ("c_attn", "kernel"), (l, c, 3 * c), 0.0, 0.02),
        (blk + ("c_attn", "bias"), (l, 3 * c), 0.0, 0.01),
        (blk + ("c_proj", "kernel"), (l, c, c), 0.0, proj_std),
        (blk + ("c_proj", "bias"), (l, c), 0.0, 0.01),
        (blk + ("ln_2", "scale"), (l, c), 1.0, 0.02),
        (blk + ("ln_2", "bias"), (l, c), 0.0, 0.01),
        (blk + ("mlp_fc", "kernel"), (l, c, 4 * c), 0.0, 0.02),
        (blk + ("mlp_fc", "bias"), (l, 4 * c), 0.0, 0.01),
        (blk + ("mlp_proj", "kernel"), (l, 4 * c, c), 0.0, proj_std),
        (blk + ("mlp_proj", "bias"), (l, c), 0.0, 0.01),
        (("ln_f", "scale"), (c,), 1.0, 0.02),
        (("ln_f", "bias"), (c,), 0.0, 0.01),
    ]


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def make_leaf(m: dict, key, index: int):
    """Leaf `index` of `leaf_table`, float32."""
    _, shape, mean, std = leaf_table(m)[index]
    k = jax.random.fold_in(key, index)
    return mean + std * jax.random.normal(k, shape, jnp.float32)


def make_params(m: dict, key) -> dict:
    """The whole parameter tree (call under `jit`)."""
    tree: dict = {}
    for i, (path, _, _, _) in enumerate(leaf_table(m)):
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = make_leaf(m, key, i)
    return tree


def leaf_name(path) -> str:
    return "/".join(path)


def get_leaf(tree, path):
    for part in path:
        tree = tree[part]
    return tree


QKV_PARTS = ("q", "k", "v")


def leaf_norms(tree, m: dict, *, minus_key=None, scale: float = 1.0) -> dict[str, float]:
    """The norm of every leaf (times `scale`), one leaf at a time; with
    `minus_key`, of the leaf less the initial leaf that key makes. The fused
    query-key-value leaves are read as three, so that the key's bias, whose
    gradient is nought under softmax, stands alone."""
    import functools

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def one(leaf, index, parts, key):
        if key is not None:
            leaf = leaf - make_leaf(m, key, index)
        return [jnp.linalg.norm(p.ravel()) for p in jnp.split(leaf, parts, axis=-1)]

    out = {}
    for i, (path, _, _, _) in enumerate(leaf_table(m)):
        fused = "c_attn" in path
        norms = one(get_leaf(tree, path), i, 3 if fused else 1, minus_key)
        for part, n in zip(QKV_PARTS if fused else ("",), norms):
            name = leaf_name(path) + (f"[{part}]" if part else "")
            out[name] = float(n) * scale
    return out
