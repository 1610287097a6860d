"""Attention stack tests: blockwise == reference, flash kernel (interpret
mode) == reference, ring attention over the 'seq' axis == single-device,
and gradients flow through all of them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuflow import dist
from tpuflow.ops.attention import attention, xla_attention
from tpuflow.ops.flash_attention import blockwise_attention, flash_attention
from tpuflow.parallel.ring_attention import ring_attention


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


def test_flash_kernel_matches_reference():
    q, k, v = _qkv(B=1, T=64, H=2, D=32)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_flash_grad_matches_reference():
    q, k, v = _qkv(B=1, T=32, H=1, D=16)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16).sum()

    def loss_ref(q, k, v):
        return xla_attention(q, k, v).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_grad_compact_lse_residual(monkeypatch):
    """TPUFLOW_FLASH_LSE=compact (the remat-off memory escape hatch)
    stores the (BH, Tq) residual and reinflates it in the backward —
    gradients must match the default full-layout path exactly."""
    q, k, v = _qkv(B=1, T=32, H=2, D=16)

    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16).sum()

    g_full = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("TPUFLOW_FLASH_LSE", "compact")
    jax.clear_caches()  # the env knob resolves at trace time
    g_compact = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_full, g_compact):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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


def test_flash_pallas_backward_multiblock():
    """The Pallas dq/dkv kernels (not the blockwise fallback) across several
    q/k blocks, causal and non-causal, against the XLA reference."""
    for causal in (True, False):
        q, k, v = _qkv(B=2, T=64, H=2, D=32)

        def loss_flash(q, k, v):
            return (
                flash_attention(
                    q, k, v, causal=causal, block_q=16, block_k=16
                )
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


def test_flash_pallas_backward_matches_blockwise_fallback(monkeypatch):
    """The kernel backward and the blockwise-recompute fallback agree."""
    q, k, v = _qkv(B=1, T=32, H=2, D=16)

    def loss(q, k, v):
        return flash_attention(q, k, v, block_q=16, block_k=16).sum()

    g_kernel = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("TPUFLOW_FLASH_BWD", "blockwise")
    g_fallback = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_kernel, g_fallback):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def _flash_grads(q, k, v, mode, causal, monkeypatch, lse=None):
    """Grads through flash_attention with TPUFLOW_FLASH_BWD=mode (and
    optionally TPUFLOW_FLASH_LSE). Fresh trace per call — both knobs
    resolve at trace time."""
    if mode is None:
        monkeypatch.delenv("TPUFLOW_FLASH_BWD", raising=False)
    else:
        monkeypatch.setenv("TPUFLOW_FLASH_BWD", mode)
    if lse is None:
        monkeypatch.delenv("TPUFLOW_FLASH_LSE", raising=False)
    else:
        monkeypatch.setenv("TPUFLOW_FLASH_LSE", lse)
    jax.clear_caches()

    def loss(q, k, v):
        return (
            flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
            * 0.1
        ).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.slow
def test_flash_bwd_fused_bit_identical_to_split(monkeypatch):
    """ISSUE 10 tentpole gate: the fused two-kernel backward (row-delta
    folded into the dq kernel's first block visit + the lane-packed
    residual feeding the merged dk/dv walk) is BIT-identical to the
    split kernels it replaces, in interpret mode, across causal/
    non-causal, both LSE residual layouts, and multiple q/k blocks —
    and the default config matches the blockwise-recompute VJP to float
    tolerance. (Tier 1 runs both LSE layouts on the causal path; the
    non-causal configs and per-config blockwise agreement ride the slow
    full-grid twin below — the 820 s guard.)"""
    for causal, lse in ((True, None), (True, "compact")):
        # 3 q/k blocks (uneven vs the 16-block), small B/H to keep the
        # interpret-mode grad compiles inside the tier-1 wall.
        q, k, v = _qkv(B=1, T=48, H=2, D=16, seed=1)
        g_fused = _flash_grads(q, k, v, None, causal, monkeypatch,
                               lse=lse)
        g_split = _flash_grads(q, k, v, "split", causal, monkeypatch,
                               lse=lse)
        for a, b, name in zip(g_fused, g_split, "qkv"):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"d{name} causal={causal} lse={lse}",
            )
        if causal and lse is None:
            g_block = _flash_grads(q, k, v, "blockwise", causal,
                                   monkeypatch, lse=lse)
            for a, b, name in zip(g_fused, g_block, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-4,
                    err_msg=f"d{name} causal={causal} lse={lse}",
                )


@pytest.mark.slow
def test_flash_bwd_fused_bit_identical_to_split_full_grid(monkeypatch):
    """The full causal × LSE-layout grid incl. the non-causal configs
    and per-config blockwise agreement (slow tier), plus the
    below-boundary fallback edge T=31 the fast twin drops."""
    q31 = _qkv(B=1, T=31, H=2, D=16, seed=31)
    g31_fused = _flash_grads(*q31, None, True, monkeypatch)
    g31_ref = jax.grad(
        lambda q, k, v: (xla_attention(q, k, v) * 0.1).sum(),
        argnums=(0, 1, 2),
    )(*q31)
    for a, b in zip(g31_fused, g31_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    for causal in (True, False):
        for lse in (None, "compact"):
            q, k, v = _qkv(B=2, T=64, H=2, D=32, seed=1)
            g_fused = _flash_grads(q, k, v, None, causal, monkeypatch,
                                   lse=lse)
            g_split = _flash_grads(q, k, v, "split", causal, monkeypatch,
                                   lse=lse)
            g_block = _flash_grads(q, k, v, "blockwise", causal,
                                   monkeypatch, lse=lse)
            for a, b, name in zip(g_fused, g_split, "qkv"):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"d{name} causal={causal} lse={lse}",
                )
            for a, b, name in zip(g_fused, g_block, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), atol=1e-4,
                    err_msg=f"d{name} causal={causal} lse={lse}",
                )


def test_flash_bwd_parity_at_block_boundary_edges(monkeypatch):
    """Odd-T edges around the block boundary (block 16; T = 31/32/33):
    the tiling T takes the kernels, the ±1 neighbors take the documented
    blockwise fallback — every mode's gradients must agree with the XLA
    reference, and fused must stay bit-identical to split where the
    kernels actually run (at the fallback T both env modes trace the
    SAME blockwise program, so only one is compiled; the below-boundary
    edge T=31 rides the slow twin)."""
    for T in (32, 33):
        q, k, v = _qkv(B=1, T=T, H=2, D=16, seed=T)
        g_ref = jax.grad(
            lambda q, k, v: (xla_attention(q, k, v) * 0.1).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_fused = _flash_grads(q, k, v, None, True, monkeypatch)
        if T % 16 == 0:
            g_split = _flash_grads(q, k, v, "split", True, monkeypatch)
            for a, b in zip(g_fused, g_split):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b)
                )
        for a, b in zip(g_fused, g_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4,
                err_msg=f"T={T}",
            )


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


def test_attention_auto_picks_xla_off_tpu(monkeypatch):
    """impl='auto' must resolve to the XLA path everywhere except a TPU
    backend at long sequence (the measured fwd+bwd crossover,
    a v5e record of 2026-07-31, since deleted: 0.2x at T=512, 1.73x at T=2048) —
    on this CPU platform it must equal xla_attention bit-for-bit at any
    length, including ones the flash kernel couldn't even tile."""
    from tpuflow.ops.attention import attention, xla_attention

    q, k, v = (
        jax.random.normal(jax.random.PRNGKey(i), (2, 48, 2, 16))
        for i in range(3)
    )
    np.testing.assert_array_equal(
        np.asarray(attention(q, k, v, causal=True, impl="auto")),
        np.asarray(xla_attention(q, k, v, causal=True)),
    )
    # The threshold is resolved at trace time (baked into compiled
    # programs); these unjitted calls re-read it, and even a tiny min_seq
    # changes nothing off-TPU.
    monkeypatch.setenv("TPUFLOW_FLASH_MIN_SEQ", "1")
    np.testing.assert_array_equal(
        np.asarray(attention(q, k, v, causal=True, impl="auto")),
        np.asarray(xla_attention(q, k, v, causal=True)),
    )


def test_flash_dispatch_independent_fwd_and_fwdbwd_thresholds(monkeypatch):
    """The measured T=512 regression (ISSUE 4 satellite): on chip, flash
    fwd wins at T=512 (2.73x) while flash fwd+bwd LOSES there (0.2x) —
    so 'auto' dispatch carries independent crossovers per path. Pins the
    shipped defaults (fwd 512, fwd+bwd 2048), the per-path env
    overrides, and that the tuning file's keys are read per path."""
    import json

    from tpuflow.ops.attention import resolve_attention_impl

    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ", raising=False)
    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ_FWD", raising=False)
    # Point the tuning file somewhere empty so host state can't leak in.
    monkeypatch.setenv("TPUFLOW_HOME", "/nonexistent_tpuflow_home")
    import importlib

    att = importlib.import_module("tpuflow.ops.attention")
    monkeypatch.setattr(att, "_flash_tuning_cache", None)

    # THE regression pin: the T=512 fwd+bwd shape must dispatch to XLA
    # while the same shape's fwd-only path takes flash.
    assert resolve_attention_impl(
        "auto", 512, needs_bwd=True, backend="tpu") == "xla"
    assert resolve_attention_impl(
        "auto", 512, needs_bwd=False, backend="tpu") == "flash"
    # Both paths win at the measured fwd+bwd crossover and above.
    assert resolve_attention_impl(
        "auto", 2048, needs_bwd=True, backend="tpu") == "flash"
    assert resolve_attention_impl(
        "auto", 2048, needs_bwd=False, backend="tpu") == "flash"
    # Below the fwd threshold everything is XLA.
    assert resolve_attention_impl(
        "auto", 256, needs_bwd=False, backend="tpu") == "xla"
    # Off-TPU is always XLA regardless of path or length.
    assert resolve_attention_impl(
        "auto", 8192, needs_bwd=True, backend="cpu") == "xla"
    assert resolve_attention_impl(
        "auto", 8192, needs_bwd=False, backend="cpu") == "xla"
    # Explicit impls pass through untouched.
    assert resolve_attention_impl(
        "ring", 8, needs_bwd=True, backend="cpu") == "ring"

    # Per-path env overrides: each knob moves only its own path.
    monkeypatch.setenv("TPUFLOW_FLASH_MIN_SEQ", "4096")
    assert resolve_attention_impl(
        "auto", 2048, needs_bwd=True, backend="tpu") == "xla"
    assert resolve_attention_impl(
        "auto", 2048, needs_bwd=False, backend="tpu") == "flash"
    monkeypatch.setenv("TPUFLOW_FLASH_MIN_SEQ_FWD", "128")
    assert resolve_attention_impl(
        "auto", 256, needs_bwd=False, backend="tpu") == "flash"
    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ")
    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ_FWD")


def test_flash_tuning_file_per_path_keys(tmp_path, monkeypatch):
    """bench.py persists {flash_min_seq, flash_min_seq_fwd}; the
    dispatcher reads each key for its own path only."""
    import json

    from tpuflow.ops.attention import resolve_attention_impl

    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ", raising=False)
    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ_FWD", raising=False)
    monkeypatch.setenv("TPUFLOW_HOME", str(tmp_path))
    with open(tmp_path / "flash_tuning.json", "w") as f:
        json.dump({"flash_min_seq": 1024, "flash_min_seq_fwd": 256}, f)
    import importlib

    att = importlib.import_module("tpuflow.ops.attention")
    monkeypatch.setattr(att, "_flash_tuning_cache", None)
    assert resolve_attention_impl(
        "auto", 1024, needs_bwd=True, backend="tpu") == "flash"
    assert resolve_attention_impl(
        "auto", 512, needs_bwd=True, backend="tpu") == "xla"
    assert resolve_attention_impl(
        "auto", 256, needs_bwd=False, backend="tpu") == "flash"
    monkeypatch.setattr(att, "_flash_tuning_cache", None)


def test_flash_tuning_bwd_only_crossover_governs_training_path(
    tmp_path, monkeypatch
):
    """ISSUE 10 satellite: the fitted bwd-ONLY crossover
    (``flash_min_seq_bwd``, from bench's T512/T2048 vjp timing split)
    raises the effective fwd+bwd threshold — below the measured
    backward-kernel loss region, auto dispatch picks XLA even when the
    fwd+bwd composition entry would have allowed flash. The fwd-only
    path never consults it; malformed entries degrade to the shipped
    default with a once-per-process warning."""
    import importlib
    import json

    from tpuflow.ops.attention import resolve_attention_impl

    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ", raising=False)
    monkeypatch.delenv("TPUFLOW_FLASH_MIN_SEQ_FWD", raising=False)
    monkeypatch.setenv("TPUFLOW_HOME", str(tmp_path))
    att = importlib.import_module("tpuflow.ops.attention")

    def retune(entries):
        with open(tmp_path / "flash_tuning.json", "w") as f:
            json.dump(entries, f)
        monkeypatch.setattr(att, "_flash_tuning_cache", None)

    # The bwd crossover is the binding constraint: max(512, 2048).
    retune({"flash_min_seq": 512, "flash_min_seq_bwd": 2048,
            "flash_min_seq_fwd": 256})
    assert resolve_attention_impl(
        "auto", 1024, needs_bwd=True, backend="tpu") == "xla"
    assert resolve_attention_impl(
        "auto", 2048, needs_bwd=True, backend="tpu") == "flash"
    # The fwd-only path is governed by its own key alone.
    assert resolve_attention_impl(
        "auto", 256, needs_bwd=False, backend="tpu") == "flash"
    # bwd entry alone still gates the training path.
    retune({"flash_min_seq_bwd": 1024})
    assert resolve_attention_impl(
        "auto", 512, needs_bwd=True, backend="tpu") == "xla"
    assert resolve_attention_impl(
        "auto", 1024, needs_bwd=True, backend="tpu") == "flash"
    # Malformed entries are ignored (warn once) → shipped default 2048.
    retune({"flash_min_seq": "garbage", "flash_min_seq_bwd": -3})
    monkeypatch.setattr(att, "_warned_malformed_tuning", False)
    with pytest.warns(UserWarning, match="flash tuning entry"):
        assert resolve_attention_impl(
            "auto", 1024, needs_bwd=True, backend="tpu") == "xla"
    assert resolve_attention_impl(
        "auto", 2048, needs_bwd=True, backend="tpu") == "flash"
    monkeypatch.setattr(att, "_flash_tuning_cache", None)
