"""FSDP / tensor-parallel sharding tests on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from tpuflow import dist
from tpuflow.models import get_model
from tpuflow.models.gpt2 import GPT2Config
from tpuflow.parallel import create_sharded_state, gpt2_tensor_rules, make_shardings
from tpuflow.train import TrainState, make_train_step


def _gpt2_init(cfg, tx):
    model = get_model("gpt2", config=cfg)

    def init_fn(rng):
        params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
        return TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    return model, init_fn


def test_fsdp_shards_large_params_and_opt_state():
    mesh = dist.make_mesh({"data": 2, "fsdp": 4})
    cfg = GPT2Config.small_test()
    model, init_fn = _gpt2_init(cfg, optax.adamw(1e-3))
    state, shardings = create_sharded_state(
        init_fn, mesh, jax.random.PRNGKey(0), fsdp=True
    )
    # Large kernels are sharded over the fsdp axes...
    wte_spec = state.params["wte"].sharding.spec
    assert any(s is not None for s in wte_spec)
    # ...and each device holds 1/8 of them (data*fsdp = 8).
    wte = state.params["wte"]
    assert wte.addressable_shards[0].data.size == wte.size // 8
    # Optimizer moments mirror the param sharding (ZeRO-3 property).
    mu_wte = state.opt_state[0].mu["wte"]
    assert mu_wte.sharding.spec == wte.sharding.spec
    # Scalars and tiny leaves stay replicated.
    assert state.step.sharding.is_fully_replicated
    ln_scale = state.params["ln_f"]["scale"]
    assert ln_scale.sharding.is_fully_replicated


def test_fsdp_train_step_matches_replicated():
    """One FSDP train step produces the same params as a replicated DP step
    (GSPMD all-gather/reduce-scatter must be numerically transparent)."""
    cfg = GPT2Config.small_test(dropout=0.0)
    tx = optax.sgd(0.1)
    tokens = np.arange(8 * 9, dtype=np.int32).reshape(8, 9) % cfg.vocab_size
    batch = {"x": tokens[:, :-1], "y": tokens[:, 1:]}
    step = make_train_step(donate=False)
    rng = jax.random.PRNGKey(0)

    mesh_fsdp = dist.make_mesh({"data": 2, "fsdp": 4})
    model, init_fn = _gpt2_init(cfg, tx)
    state_a, _ = create_sharded_state(init_fn, mesh_fsdp, jax.random.PRNGKey(7))
    state_a2, m_a = step(state_a, dist.shard_batch(batch, mesh_fsdp), rng)

    mesh_dp = dist.make_mesh({"data": 8})
    state_b, _ = create_sharded_state(
        init_fn, mesh_dp, jax.random.PRNGKey(7), fsdp=False
    )
    state_b2, m_b = step(state_b, dist.shard_batch(batch, mesh_dp), rng)

    np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(state_a2.params),
        jax.tree_util.tree_leaves(state_b2.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-5, rtol=1e-4
        )


def test_tensor_rules_column_row_split():
    mesh = dist.make_mesh({"data": 2, "tensor": 4})
    cfg = GPT2Config.small_test()
    model, init_fn = _gpt2_init(cfg, optax.sgd(0.1))
    state, _ = create_sharded_state(
        init_fn,
        mesh,
        jax.random.PRNGKey(0),
        fsdp=False,
        tensor_rules=gpt2_tensor_rules,
    )
    attn_kernel = state.params["h0"]["c_attn"]["kernel"]
    proj_kernel = state.params["h0"]["c_proj"]["kernel"]
    assert attn_kernel.sharding.spec[1] == "tensor"  # column parallel
    assert proj_kernel.sharding.spec[0] == "tensor"  # row parallel
    assert state.params["wte"].sharding.spec[0] == "tensor"
    # A forward+backward step executes under TP.
    tokens = np.arange(2 * 8, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
    step = make_train_step(donate=False)
    _, metrics = step(
        state,
        dist.shard_batch({"x": tokens[:, :-1], "y": tokens[:, 1:]}, mesh),
        jax.random.PRNGKey(1),
    )
    assert np.isfinite(float(metrics["loss"]))


def test_make_shardings_respects_divisibility():
    mesh = dist.make_mesh({"data": 8})
    tree = {
        "odd": jax.ShapeDtypeStruct((7, 7), jnp.float32),
        "big": jax.ShapeDtypeStruct((16, 4096), jnp.float32),
    }
    sh = make_shardings(tree, mesh, fsdp=True)
    assert sh["odd"].spec == jax.sharding.PartitionSpec(None, None)
    assert any(s is not None for s in sh["big"].spec)


def test_expert_weights_shard_over_expert_axis():
    """gpt2_tensor_rules places MoE expert weights on the 'expert' mesh axis
    (the flow passes the rules whenever --expert-axis > 1; regression for
    the silently-replicated-experts bug)."""
    import jax.numpy as jnp
    import optax

    from tpuflow import dist
    from tpuflow.models.gpt2 import GPT2, GPT2Config
    from tpuflow.parallel import (
        create_sharded_state,
        gpt2_tensor_rules,
        has_sharded_leaf,
    )
    from tpuflow.train import TrainState

    mesh = dist.make_mesh({"data": 2, "expert": 4})
    cfg = GPT2Config.small_test(n_experts=4, dropout=0.0)
    model = GPT2(cfg)

    def init_fn(rng):
        params = model.init(rng, jnp.zeros((1, 8), jnp.int32))["params"]
        return TrainState.create(
            apply_fn=model.apply, params=params, tx=optax.adamw(1e-3)
        )

    with mesh:
        state, shardings = create_sharded_state(
            init_fn,
            mesh,
            jax.random.PRNGKey(0),
            fsdp=True,
            tensor_rules=gpt2_tensor_rules,
        )
    assert has_sharded_leaf(shardings, axis="expert")
    assert "expert" in str(state.params["h0"]["moe"]["w1"].sharding.spec)


def test_pin_batch_keeps_activations_split_when_the_vocab_does_not_divide():
    """A vocabulary the FSDP world does not divide (GPT-2's 50,257 on four
    chips; 509 on eight devices here) makes FSDP split ``wte``'s hidden
    axis, and without a pin the embedding lookup hands that layout to the
    activations: every device then computes the WHOLE batch. With
    ``pin_batch`` after the embedding each device computes the logits of
    its own batch rows only."""
    import re

    from tpuflow.parallel.sharding import pin_batch

    B, T, C = 8, 16, 128
    mesh = dist.make_mesh({"data": 1, "fsdp": 8})
    cfg = GPT2Config.small_test(vocab_size=509, n_embd=C, dropout=0.0)
    model, init_fn = _gpt2_init(cfg, optax.adamw(1e-3))
    with mesh:
        state, _ = create_sharded_state(
            init_fn, mesh, jax.random.PRNGKey(0), fsdp=True,
            materialize=False,
        )
        assert state.params["wte"].sharding.spec == (None, "fsdp")
        bs = dist.batch_sharding(mesh, 2)
        batch = {
            k: jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=bs)
            for k in ("x", "y")
        }
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=dist.replicated(mesh))
        hlo = make_train_step().lower(state, batch, rng).compile().as_text()
        # Leading dim only, and only where a mesh is active and divides.
        x = jnp.zeros((B, T, C))
        pinned = jax.jit(pin_batch)(x)
        assert pinned.sharding.spec[0] == "fsdp"
        assert jax.jit(pin_batch)(x[:3]).sharding.is_fully_replicated
    # The logits are the step's largest buffer: every device must hold its
    # own rows of them and never the whole batch's (without the pin this
    # program has f32[8,16,509] buffers on every device).
    rows = {int(b) for b in re.findall(rf"f32\[(\d+),{T},509\]", hlo)}
    assert rows == {B // 8}, rows
    assert pin_batch(x) is x  # no mesh: untouched
