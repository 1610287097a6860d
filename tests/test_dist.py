"""Unit tests for the dist facade (mesh, shardings, batch placement)."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tpuflow import dist


def test_make_mesh_default_all_data():
    mesh = dist.make_mesh()
    assert mesh.shape["data"] == len(jax.devices())
    # Canonical axes always present so sharding rules resolve on any mesh.
    for name in ("data", "fsdp", "tensor", "seq"):
        assert name in mesh.shape


def test_make_mesh_infer_axis():
    mesh = dist.make_mesh({"data": -1, "tensor": 2})
    assert mesh.shape["data"] == len(jax.devices()) // 2
    assert mesh.shape["tensor"] == 2


def test_make_mesh_bad_total():
    with pytest.raises(ValueError):
        dist.make_mesh({"data": 3})


def test_data_axis_size(mesh8):
    assert dist.data_axis_size(mesh8) == 8
    mesh = dist.make_mesh({"data": 2, "fsdp": 4})
    assert dist.data_axis_size(mesh) == 8


def test_shard_batch_layout(mesh8):
    batch = {"x": np.zeros((16, 28, 28), np.float32), "y": np.zeros((16,), np.int32)}
    placed = dist.shard_batch(batch, mesh8)
    # Leading dim split 8 ways: each device holds 2 rows.
    shard_shapes = {s.data.shape for s in placed["x"].addressable_shards}
    assert shard_shapes == {(2, 28, 28)}
    assert placed["y"].sharding.spec == P(("data", "fsdp"))


def test_replicated(mesh8):
    x = jax.device_put(np.ones((4, 4), np.float32), dist.replicated(mesh8))
    assert x.sharding.is_fully_replicated


def test_initialize_single_process_noop():
    dist.initialize()  # no coordinator → no-op, must not raise
    assert not dist.is_initialized()
    dist.barrier()  # single-process barrier is a no-op


def test_shard_batch_small_batch_replicates(mesh8):
    """Batches smaller than (or not divisible by) the data-shard count take
    the documented replicate fallback instead of raising (VERDICT r1 #2)."""
    batch = {"x": np.zeros((2, 16), np.float32), "y": np.zeros((2,), np.int32)}
    placed = dist.shard_batch(batch, mesh8)
    assert placed["x"].sharding.is_fully_replicated
    assert placed["y"].sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(placed["x"]), batch["x"])


def test_dp8_numerics_match_single_device(mesh8):
    """SURVEY §4: the allreduced gradients of an 8-shard data-parallel step
    must equal the single-device gradients on identical data — the property
    DDP guarantees in the reference (my_ray_module.py:135,159)."""
    import optax

    from tpuflow.models.mlp import NeuralNetwork
    from tpuflow.train import create_train_state, make_train_step

    model = NeuralNetwork(dropout_rate=0.0)
    rng = jax.random.PRNGKey(0)
    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (16, 28, 28)), np.float32
    )
    y = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 10))
    tx = optax.sgd(0.1, momentum=0.9)

    def run(mesh):
        state = create_train_state(model, rng, x[:1], tx)
        with mesh:
            batch = dist.shard_batch({"x": x, "y": y}, mesh)
            state = state.replace(params=dist.replicate(state.params, mesh))
            new_state, metrics = make_train_step(donate=False)(
                state, batch, jax.random.PRNGKey(3)
            )
        return float(metrics["loss"]), jax.device_get(new_state.params)

    mesh1 = dist.make_mesh({"data": 1}, devices=jax.devices()[:1])
    loss1, params1 = run(mesh1)
    loss8, params8 = run(mesh8)
    assert abs(loss1 - loss8) < 1e-5
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        params1,
        params8,
    )


def test_topology_change_restore_identical_forward(tmp_path, mesh8):
    """SURVEY §4: a state FSDP-sharded over K=8 devices, checkpointed, then
    restored onto a K'=4 mesh must produce bit-identical forward outputs."""
    import jax.numpy as jnp
    import optax

    from tpuflow.ckpt import CheckpointManager
    from tpuflow.models.mlp import NeuralNetwork
    from tpuflow.parallel import create_sharded_state, make_shardings
    from tpuflow.train import create_train_state

    model = NeuralNetwork(dropout_rate=0.0)
    rng = jax.random.PRNGKey(0)
    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (8, 28, 28)), np.float32
    )
    tx = optax.sgd(0.1)

    state, _ = create_sharded_state(
        lambda: create_train_state(model, rng, x[:1], tx),
        mesh8,
        fsdp=True,
    )
    # Forward on host-materialized params: sharded eager execution reorders
    # reductions (~1e-7 noise), so bit-exactness is asserted on identical
    # (host) layouts on both sides of the round-trip.
    ref_out = np.asarray(
        model.apply({"params": jax.device_get(state.params)}, x)
    )

    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"params": state.params}, metrics={"val_loss": 1.0})
    mgr.close()

    mesh4 = dist.make_mesh({"data": 2, "fsdp": 2}, devices=jax.devices()[:4])
    abstract = jax.eval_shape(lambda t: t, state.params)
    shardings4 = make_shardings(abstract, mesh4, fsdp=True)
    target = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract,
        shardings4,
    )
    mgr2 = CheckpointManager(str(tmp_path), async_save=False)
    restored = mgr2.restore(1, abstract_state={"params": target})
    mgr2.close()
    assert restored["params"]["dense1"]["kernel"].sharding.mesh.shape["fsdp"] == 2
    out4 = np.asarray(
        model.apply({"params": jax.device_get(restored["params"])}, x)
    )
    np.testing.assert_array_equal(ref_out, out4)


class _FakeDev:
    """Stand-in device with the attributes TPU runtimes expose — enough for
    mesh_utils.create_hybrid_device_mesh's REAL path to run (not just our
    fallback), so the shape-interleaving call stays covered."""

    def __init__(self, i, slice_index):
        self.id = i
        self.slice_index = slice_index
        self.platform = "cpu"
        self.device_kind = "cpu"
        self.process_index = slice_index

    def __repr__(self):
        return f"dev{self.id}@slice{self.slice_index}"


def test_hybrid_mesh_dcn_outer_ici_inner():
    """make_hybrid_mesh places DCN axes outermost (whole slices per index)
    and ICI axes within a slice — cross-slice collectives only on the DCN
    axes."""
    from tpuflow.dist import make_hybrid_mesh

    devs = [_FakeDev(i, slice_index=i // 4) for i in range(8)]  # 2 slices x 4
    mesh = make_hybrid_mesh({"data": 2}, {"fsdp": 4}, devices=devs)
    assert mesh.axis_names[:2] == ("data", "fsdp")
    assert dict(mesh.shape)["data"] == 2 and dict(mesh.shape)["fsdp"] == 4
    arr = np.asarray(mesh.devices).reshape(2, -1)
    # Each 'data' index holds exactly one slice's devices.
    for row in range(2):
        assert {d.slice_index for d in arr[row].ravel()} == {row}


def test_hybrid_mesh_validates_slices_and_overlap():
    from tpuflow.dist import make_hybrid_mesh

    devs = [_FakeDev(i, slice_index=i // 4) for i in range(8)]
    with pytest.raises(ValueError, match="slices"):
        make_hybrid_mesh({"data": 4}, {"fsdp": 2}, devices=devs)
    with pytest.raises(ValueError, match="both"):
        make_hybrid_mesh({"data": 2}, {"data": 4}, devices=devs)
    # DCN product 1 degrades to plain make_mesh on real devices.
    import jax

    mesh = make_hybrid_mesh({}, {"data": 8}, devices=jax.devices())
    assert dict(mesh.shape)["data"] == 8


def test_hybrid_mesh_rejects_minus_one():
    from tpuflow.dist import make_hybrid_mesh

    devs = [_FakeDev(i, slice_index=i // 4) for i in range(8)]
    with pytest.raises(ValueError, match="-1"):
        make_hybrid_mesh({"data": 2}, {"fsdp": -1}, devices=devs)


def test_persistent_compile_cache_hits_across_processes(tmp_path):
    """With the cache placed by JAX_COMPILATION_CACHE_DIR, a second
    PROCESS running the same jit program loads the compiled executable
    instead of recompiling (what amortizes TPU compiles across
    retries/resumes/eval flows). CPU processes need the explicit
    TPUFLOW_COMPILE_CACHE_CPU=1 opt-in: jaxlib's CPU AOT reload path is
    unsafe (machine-feature mismatch aborts), so by default the cache
    only engages on accelerator platforms — pinned at the end."""
    import os
    import subprocess
    import sys

    cache_dir = tmp_path / "cc"
    prog = (
        "import os\n"
        "from tpuflow.dist import force_cpu_platform, "
        "maybe_enable_compile_cache\n"
        "force_cpu_platform(1)\n"
        "d = maybe_enable_compile_cache()\n"
        "assert d == os.environ['JAX_COMPILATION_CACHE_DIR'], d\n"
        "import jax, jax.numpy as jnp\n"
        # Force even this fast-compiling test program into the cache.
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
        "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "f = jax.jit(lambda x: jnp.tanh(x @ x).sum())\n"
        "f(jnp.ones((64, 64))).block_until_ready()\n"
    )
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(cache_dir),
           "TPUFLOW_FORCE_CPU": "1", "TPUFLOW_COMPILE_CACHE_CPU": "1"}
    p1 = subprocess.run(
        [sys.executable, "-c", prog], env=env, capture_output=True,
        text=True, timeout=180,
    )
    assert p1.returncode == 0, p1.stderr[-2000:]
    entries = os.listdir(cache_dir)
    assert entries, "first process wrote no cache entries"
    # Second process: same program, same cache — must not ADD entries
    # (every compile is served from the cache) and must still succeed.
    p2 = subprocess.run(
        [sys.executable, "-c", prog], env=env, capture_output=True,
        text=True, timeout=180,
    )
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert set(os.listdir(cache_dir)) == set(entries)
    # TPUFLOW_COMPILE_CACHE=0 disables cleanly even with the CPU opt-in.
    env_off = {**env, "TPUFLOW_COMPILE_CACHE": "0"}
    p3 = subprocess.run(
        [sys.executable, "-c",
         "from tpuflow.dist import maybe_enable_compile_cache\n"
         "assert maybe_enable_compile_cache() is None\n"],
        env=env_off, capture_output=True, text=True, timeout=120,
    )
    assert p3.returncode == 0, p3.stderr[-2000:]
    # Default CPU policy: SKIPPED (no opt-in) — the unsafe AOT reload
    # path must never engage for test/gang/bench CPU processes.
    env_cpu_default = {k: v for k, v in env.items()
                       if k != "TPUFLOW_COMPILE_CACHE_CPU"}
    p4 = subprocess.run(
        [sys.executable, "-c",
         "from tpuflow.dist import force_cpu_platform, "
         "maybe_enable_compile_cache\n"
         "force_cpu_platform(1)\n"
         "assert maybe_enable_compile_cache() is None\n"],
        env=env_cpu_default, capture_output=True, text=True, timeout=120,
    )
    assert p4.returncode == 0, p4.stderr[-2000:]


def test_step_fence_serializes_only_on_cpu_simulation():
    """The oversubscribed-CPU predicate gates the hot-loop fence: on this
    8-virtual-device CPU test platform it must say 'serialize', and
    step_fence must force completion while passing its argument through
    (the regression it guards: XLA:CPU's 40s collective-rendezvous
    termination killing the MLP flow's async-dispatched epoch)."""
    import jax.numpy as jnp

    from tpuflow import dist

    assert dist.serialize_steps() is True
    mesh = dist.make_mesh({"data": len(jax.devices())})
    x = dist.replicate(jnp.arange(8.0), mesh)
    y = jax.jit(lambda v: v * 2)(x)
    out = dist.step_fence(y)
    assert out is y
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0) * 2)


def test_platform_is_cpu_decides_without_touching_a_backend():
    """The pre-init platform question (gang launcher, libtpu flag staging,
    compile-cache policy) is answered from jax.config.jax_platforms /
    JAX_PLATFORMS alone: asking must never initialize a backend — a parent
    that did would hold the chip its children need — and no selection at
    all means JAX's default platform, i.e. not the CPU."""
    import os
    import subprocess
    import sys

    assert dist.platform_is_cpu() is True  # conftest pinned this process
    prog = (
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from tpuflow import dist\n"
        "print(dist.platform_is_cpu(), xla_bridge.backends_are_initialized())\n"
    )
    base = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    for selected, want in (("tpu,cpu", "False"), (None, "False")):
        env = dict(base) if selected is None else {**base, "JAX_PLATFORMS": selected}
        p = subprocess.run(
            [sys.executable, "-c", prog], env=env, capture_output=True,
            text=True, timeout=120,
        )
        assert p.returncode == 0, p.stderr[-2000:]
        assert p.stdout.split() == [want, "False"], (selected, p.stdout)


def test_compile_cache_is_placed_from_outside_or_fixed(tmp_path, monkeypatch):
    """The cache directory is part of every entry's key, so it must not
    move. JAX_COMPILATION_CACHE_DIR set: nothing is set in code and that
    directory is returned. Unset: the one fixed in-checkout directory,
    whatever TPUFLOW_HOME says. CPU without the opt-in: excluded."""
    import os

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    monkeypatch.delenv("TPUFLOW_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("TPUFLOW_COMPILE_CACHE_CPU", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert dist.maybe_enable_compile_cache() is None  # CPU: excluded
    monkeypatch.setenv("TPUFLOW_COMPILE_CACHE_CPU", "1")
    assert dist.maybe_enable_compile_cache() == str(tmp_path / "placed")
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for home in ("home_a", "home_b"):
        monkeypatch.setenv("TPUFLOW_HOME", str(tmp_path / home))
        assert dist.maybe_enable_compile_cache() == os.path.join(
            repo, ".compile_cache"
        )
    assert updates == [
        ("jax_compilation_cache_dir", dist.COMPILE_CACHE_DIR)
    ] * 2
    assert not os.path.exists(tmp_path / "home_a")
