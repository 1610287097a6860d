"""Attention stack tests: blockwise == reference, flash kernel (interpret
mode) == reference, ring attention over the 'seq' axis == single-device,
and gradients flow through all of them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuflow import dist
from tpuflow.ops import flash_attention as fa
from tpuflow.ops.attention import attention, xla_attention
from tpuflow.ops.flash_attention import blockwise_attention, flash_attention
from tpuflow.parallel.ring_attention import ring_attention


@pytest.fixture
def small_tiles(monkeypatch):
    """The kernels choose their blocks from the sequence lengths alone
    (ISSUE 31); the interpreter cannot afford the real ones on every
    test, so these steer the two constants the choice is made from:
    score tiles of 16 and row blocks of 32, so that a 64-position row
    walks 2 x 2 grid steps of 2 x 2 tiles (the online softmax, the
    accumulators carried in scratch, the causal skip) and a 32- or
    48-position row is one step of several tiles."""
    monkeypatch.setattr(fa, "_SUB", 16)
    monkeypatch.setattr(fa, "_BLOCKS", (32,))


def _qkv(B=2, T=64, H=2, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


def test_blockwise_matches_reference_causal():
    q, k, v = _qkv()
    ref = xla_attention(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_blockwise_matches_reference_noncausal():
    q, k, v = _qkv(T=48)
    ref = xla_attention(q, k, v, causal=False)
    out = blockwise_attention(q, k, v, causal=False, block_k=24)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_kernel_matches_reference(small_tiles):
    q, k, v = _qkv(B=1, T=64, H=2, D=32)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_flash_grad_matches_reference(small_tiles):
    q, k, v = _qkv(B=1, T=32, H=1, D=16)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v).sum()

    def loss_ref(q, k, v):
        return xla_attention(q, k, v).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_ungroupable_heads_take_blockwise_off_the_chip():
    """Three heads of 64 fill no whole number of 128-lane tiles: the
    kernels do not run that shape (``flash_tiles``), so ``impl='flash'``
    raises on the chip and here, in interpret mode, takes the blockwise
    path — the same math, values and gradients."""
    q, k, v = _qkv(B=1, T=32, H=3, D=64)
    assert fa._head_group(3, 64) is None
    assert not fa.flash_tiles(32, 32, 3, 64)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * 0.1).sum()

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v)),
        np.asarray(xla_attention(q, k, v)), atol=1e-5,
    )
    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_ring_attention_matches_single_device():
    mesh = dist.make_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(B=2, T=64, H=2, D=16)
    ref = xla_attention(q, k, v, causal=True)
    with mesh:
        out = ring_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # And under jit with sharded inputs (the training-step configuration).
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "fsdp"), "seq", None, None)
    )
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    with mesh:
        out_jit = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=mesh))(
            qs, ks, vs
        )
    np.testing.assert_allclose(np.asarray(out_jit), np.asarray(ref), atol=1e-5)


def test_ring_attention_grads_flow():
    mesh = dist.make_mesh({"seq": 8})
    q, k, v = _qkv(B=1, T=32, H=1, D=8, seed=3)

    def loss_ring(q, k, v):
        return ring_attention(q, k, v, mesh=mesh).sum()

    def loss_ref(q, k, v):
        return xla_attention(q, k, v).sum()

    with mesh:
        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_attention_dispatch():
    q, k, v = _qkv(B=1, T=16, H=1, D=8)
    ref = attention(q, k, v, impl="xla")
    fl = attention(q, k, v, impl="flash")
    np.testing.assert_allclose(np.asarray(fl), np.asarray(ref), atol=1e-4)
    with pytest.raises(KeyError):
        attention(q, k, v, impl="nope")


def test_gpt2_with_ring_attention_trains():
    """GPT-2 with attn_impl='ring' runs a full train step on a seq-sharded
    mesh — the long-context training configuration."""
    import optax

    from tpuflow.models.gpt2 import GPT2, GPT2Config
    from tpuflow.parallel import create_sharded_state
    from tpuflow.train import TrainState, make_train_step

    mesh = dist.make_mesh({"data": 2, "seq": 4})
    cfg = GPT2Config.small_test(attn_impl="ring", dropout=0.0, n_ctx=64)
    model = GPT2(cfg)
    tx = optax.sgd(0.1)

    def init_fn(rng):
        params = model.init(rng, jnp.zeros((1, 32), jnp.int32))["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    with mesh:
        state, _ = create_sharded_state(
            init_fn, mesh, jax.random.PRNGKey(0), fsdp=False
        )
        tokens = np.arange(2 * 33, dtype=np.int32).reshape(2, 33) % cfg.vocab_size
        batch = {
            "x": jax.device_put(
                tokens[:, :-1],
                jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(("data", "fsdp"), "seq")
                ),
            ),
            "y": jax.device_put(
                tokens[:, 1:],
                jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(("data", "fsdp"), "seq")
                ),
            ),
        }
        step = make_train_step(donate=False)
        state2, metrics = step(state, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(metrics["loss"]))
    # Params actually changed.
    a = jax.tree_util.tree_leaves(state.params)[0]
    b = jax.tree_util.tree_leaves(state2.params)[0]
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_flash_pallas_backward_multiblock(small_tiles):
    """The Pallas backward kernel (not the blockwise fallback) across several
    q/k blocks, causal and non-causal, against the XLA reference."""
    for causal in (True, False):
        q, k, v = _qkv(B=2, T=64, H=2, D=32)

        def loss_flash(q, k, v):
            return (
                flash_attention(q, k, v, causal=causal)
                * 0.1
            ).sum()

        def loss_ref(q, k, v):
            return (xla_attention(q, k, v, causal=causal) * 0.1).sum()

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4
            )


def _grads(fn, q, k, v, causal=True):
    def loss(q, k, v):
        return (fn(q, k, v, causal=causal) * 0.1).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def test_flash_pallas_backward_matches_blockwise_fallback(small_tiles):
    """The kernel backward agrees with ``jax.grad`` of the pure-JAX
    ``blockwise_attention`` (the off-chip path of odd shapes)."""
    q, k, v = _qkv(B=1, T=32, H=2, D=16)
    g_kernel = _grads(flash_attention, q, k, v)
    g_blockwise = _grads(blockwise_attention, q, k, v)
    for a, b in zip(g_kernel, g_blockwise):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize(
    "T", [pytest.param(31, marks=pytest.mark.slow), 32, 33]
)
def test_flash_bwd_parity_at_block_boundary_edges(T, small_tiles):
    """Odd-T edges around the tile boundary (tile 16; T = 31/32/33): the
    tiling T takes the kernels, the ±1 neighbors take the documented
    blockwise fallback — either way the gradients agree with the XLA
    reference."""
    q, k, v = _qkv(B=1, T=T, H=2, D=16, seed=T)
    g_flash = _grads(flash_attention, q, k, v)
    g_ref = _grads(xla_attention, q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=f"T={T}"
        )


def _cell_shape_parity(B, H, causal):
    """Forward and gradient parity against ``xla_attention`` in float32 at
    the training cell's row: T = 1,024, head size 64, the blocks the shape
    chooses (one 1,024 block, 256 x 256 score tiles, two heads a program)."""
    assert fa._block_sizes(1024, 1024) == (1024, 1024, 256, 256)
    assert fa._head_group(H, 64) == 2
    q, k, v = _qkv(B=B, T=1024, H=H, D=64, seed=7)
    g = jax.random.normal(jax.random.PRNGKey(11), q.shape, jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v, causal=causal) * g).sum()

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=causal)),
        np.asarray(xla_attention(q, k, v, causal=causal)), atol=2e-5,
    )
    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_parity_at_the_cell_row(causal):
    _cell_shape_parity(1, 2, causal)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_parity_at_the_cell_row_heavy_grid(causal):
    """Two batch rows x two head groups: the grid's outer axes."""
    _cell_shape_parity(2, 4, causal)


@pytest.mark.parametrize(
    "tq,tk,want",
    [
        (1024, 1024, (1024, 1024, 256, 256)),  # whole-row K/V
        (512, 512, (512, 512, 256, 256)),
        (768, 768, (256, 256, 256, 256)),
        (2048, 2048, (1024, 1024, 256, 256)),  # longer rows walk the grid
        (1536, 1536, (512, 512, 256, 256)),
        (64, 64, (64, 64, 64, 64)),  # a short row is one tile
        (256, 1024, (256, 1024, 256, 256)),
    ],
)
def test_flash_blocks_follow_the_sequence_lengths(tq, tk, want):
    assert fa._block_sizes(tq, tk) == want
    assert fa.flash_tiles(tq, tk, 20, 64, causal=False)
    assert fa.flash_tiles(tq, tk, 20, 64) == (tq == tk)


@pytest.mark.parametrize(
    "h,d,want",
    [(20, 64, 2), (12, 64, 2), (16, 64, 2), (8, 128, 1), (2, 256, 1),
     (4, 32, 4), (1, 64, 1),
     # no tile-filling group, but all heads fit one tile (the test widths)
     (2, 16, 2), (4, 16, 4), (16, 8, 16),
     # neither: the kernels do not run the shape (gpt2-xl: 25 heads of 64)
     (25, 64, None), (3, 64, None), (4, 96, None), (8, 80, None)],
)
def test_flash_head_group_fills_the_lanes(h, d, want):
    """One head where it fills whole 128-lane tiles, else the heads that
    fill one tile, else all of them where all fit one tile; otherwise no
    group: a wider one would cost groups-squared scratch (164 MB at 25
    heads of 64) and contract every head over all its lanes."""
    assert fa._head_group(h, d) == want
    assert fa.flash_tiles(1024, 1024, h, d) == (want is not None)


def test_ring_attention_ragged_T_falls_back():
    """T not divisible by the ring size takes the documented blockwise
    fallback instead of a shard_map error, and under jax.set_mesh (the
    supported mesh context) the ring still matches the reference."""
    mesh = dist.make_mesh({"seq": 8})
    q, k, v = _qkv(B=1, T=36, H=1, D=8)  # 36 % 8 != 0
    with mesh:
        out = ring_attention(q, k, v, causal=True)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    q, k, v = _qkv(B=1, T=64, H=1, D=8)
    with jax.set_mesh(mesh):
        out = ring_attention(q, k, v, causal=True)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ------------------------------------------------- ulysses (all-to-all SP)
def test_ulysses_attention_matches_single_device():
    from tpuflow.parallel.ulysses import ulysses_attention

    mesh = dist.make_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(B=2, T=64, H=4, D=16)
    ref = xla_attention(q, k, v, causal=True)
    with mesh:
        out = ulysses_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # Under jit with seq-sharded inputs (the training-step configuration).
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "fsdp"), "seq", None, None)
    )
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))
    with mesh:
        out_jit = jax.jit(
            lambda q, k, v: ulysses_attention(q, k, v, mesh=mesh)
        )(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out_jit), np.asarray(ref), atol=1e-5)


def test_ulysses_attention_grads_match():
    from tpuflow.parallel.ulysses import ulysses_attention

    mesh = dist.make_mesh({"seq": 8})
    q, k, v = _qkv(B=1, T=32, H=8, D=8, seed=3)

    def loss_uly(q, k, v):
        return ulysses_attention(q, k, v, mesh=mesh).sum()

    def loss_ref(q, k, v):
        return xla_attention(q, k, v).sum()

    with mesh:
        g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ulysses_ragged_heads_fall_back():
    """H not divisible by the seq axis → defined blockwise fallback, same
    numerics, no shard_map error."""
    from tpuflow.parallel.ulysses import ulysses_attention

    mesh = dist.make_mesh({"seq": 8})
    q, k, v = _qkv(B=1, T=32, H=3, D=8)  # 3 heads % 8 != 0
    ref = xla_attention(q, k, v, causal=True)
    with mesh:
        out = ulysses_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_gpt2_with_ulysses_attention_trains():
    """GPT-2 with attn_impl='ulysses' runs a full train step on a
    seq-sharded mesh."""
    import optax

    from tpuflow.models.gpt2 import GPT2, GPT2Config
    from tpuflow.parallel import create_sharded_state
    from tpuflow.train import TrainState, make_train_step

    cfg = GPT2Config.small_test(attn_impl="ulysses", n_ctx=64)
    mesh = dist.make_mesh({"data": 2, "seq": 4})
    model = GPT2(cfg)

    def init_fn(rng):
        params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(1e-3)
        )

    with mesh:
        state, _ = create_sharded_state(
            init_fn, mesh, jax.random.PRNGKey(0), fsdp=False
        )
        tokens = np.arange(4 * 65, dtype=np.int32).reshape(4, 65) % cfg.vocab_size
        spec = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(("data", "fsdp"), "seq")
        )
        batch = {
            "x": jax.device_put(tokens[:, :-1], spec),
            "y": jax.device_put(tokens[:, 1:], spec),
        }
        step = make_train_step(donate=False)
        new_state, metrics = step(state, batch, jax.random.PRNGKey(1))
        jax.block_until_ready(new_state.params)
    assert np.isfinite(float(metrics["loss"]))


def test_attention_auto_picks_xla_off_tpu():
    """impl='auto' must resolve to the XLA path everywhere except a TPU
    backend from the measured threshold (1,024 positions: the chip
    calls of PR 31) — on this CPU platform it must equal
    xla_attention bit-for-bit at any length, including ones the flash
    kernel couldn't even tile."""
    from tpuflow.ops.attention import attention, xla_attention

    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (2, 48, 2, 16))
        for i in range(3)
    )
    np.testing.assert_array_equal(
        np.asarray(attention(q, k, v, causal=True, impl="auto")),
        np.asarray(xla_attention(q, k, v, causal=True)),
    )

@pytest.mark.parametrize(
    "q_shape,tk,causal,mesh_axes,want",
    [
        ((8, 1024, 20, 64), 1024, True, None, True),  # the training cell
        ((8, 1024, 12, 64), 1024, True, {"data": 1, "fsdp": 1}, True),
        ((1, 600, 12, 64), 600, True, None, False),  # does not tile
        ((1, 1024, 25, 64), 1024, True, None, False),  # gpt2-xl's heads
        ((8, 1024, 12, 64), 2048, True, None, False),  # causal, Tq != Tk
        ((8, 1024, 12, 64), 2048, False, None, True),
        # A Mosaic kernel cannot be partitioned: under a mesh of several
        # devices (chip_smoke.py on four chips) 'auto' stays with XLA.
        ((8, 1024, 12, 64), 1024, True, {"data": 2, "fsdp": 4}, False),
        ((8, 1024, 12, 64), 1024, True, {"fsdp": 8}, False),
    ],
)
def test_auto_takes_the_kernels_only_where_they_run(
    q_shape, tk, causal, mesh_axes, want
):
    import contextlib

    from tpuflow.ops.attention import resolve_attention_impl

    if mesh_axes is None:
        ctx = contextlib.nullcontext()
    else:
        n = int(np.prod(list(mesh_axes.values())))
        ctx = jax.sharding.Mesh(
            np.array(jax.devices()[:n]).reshape(tuple(mesh_axes.values())),
            tuple(mesh_axes),
        )
    with ctx:
        assert resolve_attention_impl(
            "auto", q_shape, tk, causal=causal, backend="tpu"
        ) == ("flash" if want else "xla")


def test_auto_under_a_mesh_compiles_xla_attention(monkeypatch):
    """Where backend and length say flash (forced here as on a TPU) and
    the call is traced under a mesh of several devices, the jitted
    program holds no kernel and equals XLA's to the bit; outside the mesh
    the same call takes the kernel."""
    import importlib

    att = importlib.import_module("tpuflow.ops.attention")
    resolve = att.resolve_attention_impl
    monkeypatch.setattr(
        att, "resolve_attention_impl",
        lambda *a, **kw: resolve(*a, **{**kw, "backend": "tpu"}),
    )
    monkeypatch.setattr(att, "_FLASH_MIN_SEQ", 32)
    q, k, v = _qkv(B=8, T=32, H=2, D=16)
    fn = jax.jit(lambda q, k, v: attention(q, k, v, impl="auto"))
    mesh = dist.make_mesh({"data": 2, "fsdp": 4})
    with mesh:
        assert "pallas_call" not in str(jax.make_jaxpr(fn)(q, k, v))
        np.testing.assert_array_equal(
            np.asarray(fn(q, k, v)), np.asarray(xla_attention(q, k, v))
        )
    jax.clear_caches()
    assert "pallas_call" in str(jax.make_jaxpr(fn)(q, k, v))


# A training batch and a one-row dense prefill at each length.
_TRAIN = (8, 20, 64)
_PREFILL = (1, 16, 64)


@pytest.mark.parametrize(
    "seq,bhd,backend,want",
    [
        # THE pin of ISSUE 31: the training cell's row takes the kernel.
        (1024, _TRAIN, "tpu", "flash"),
        (1024, _PREFILL, "tpu", "flash"),
        # Below 1,024 positions the winner depends on batch x heads (XLA
        # by up to 3x at 80 rows of 256 or 512, the kernels by 1.2x and
        # 2.6x at 8,192 tokens: chip calls 93 and 95), so XLA keeps them.
        (512, _TRAIN, "tpu", "xla"),
        (512, _PREFILL, "tpu", "xla"),
        (256, _TRAIN, "tpu", "xla"),
        (256, _PREFILL, "tpu", "xla"),
        (2048, _TRAIN, "tpu", "flash"),
        (2048, _PREFILL, "tpu", "flash"),
        (128, _TRAIN, "tpu", "xla"),
        (128, _PREFILL, "tpu", "xla"),
        # Off-TPU is always XLA regardless of shape or length.
        (8192, _TRAIN, "cpu", "xla"),
        (8192, _PREFILL, "cpu", "xla"),
    ],
)
def test_flash_dispatch_threshold(seq, bhd, backend, want):
    """'auto' carries one threshold, for differentiated and forward-only
    calls alike: 1,024 positions (the chip calls of PR 31, PERF.md §6)."""
    from tpuflow.ops.attention import resolve_attention_impl

    b, h, d = bhd
    assert resolve_attention_impl(
        "auto", (b, seq, h, d), seq, backend=backend) == want


def test_flash_dispatch_passes_named_impls_through():
    import importlib

    from tpuflow.ops.attention import resolve_attention_impl

    assert importlib.import_module(
        "tpuflow.ops.attention")._FLASH_MIN_SEQ == 1024
    for impl in ("xla", "flash", "ring", "ulysses"):
        assert resolve_attention_impl(
            impl, (1, 8, 2, 16), 8, backend="cpu") == impl
