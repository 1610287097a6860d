"""Checkpoint subsystem tests: round-trip, best/latest policies, retention,
weights-only parity restore, and restore-across-topologies (SURVEY.md §4)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpuflow import dist
from tpuflow.ckpt import Checkpoint, CheckpointManager, restore_from_handle
from tpuflow.models import NeuralNetwork
from tpuflow.train import create_train_state


def _state(seed=0):
    model = NeuralNetwork(hidden_dim=32)
    return create_train_state(
        model,
        jax.random.PRNGKey(seed),
        jnp.zeros((1, 28, 28)),
        optax.sgd(1e-3, momentum=0.9),
    )


def _tree(state):
    """Checkpoint payload: the parity dict {step, params, opt_state}
    (↔ my_ray_module.py:183-185)."""
    return {"step": state.step, "params": state.params, "opt_state": state.opt_state}


def test_save_restore_roundtrip(tmp_path):
    state = _state()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, _tree(state), metrics={"val_loss": 0.5, "accuracy": 0.8})
    restored = mgr.restore(1)
    for a, b in zip(
        jax.tree_util.tree_leaves(_tree(state)),
        jax.tree_util.tree_leaves(restored),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_best_latest_policies_and_retention(tmp_path):
    """val_loss sequence 0.9, 0.4, 0.7, 0.6 with max_to_keep=2:
    latest=4, best=2, and step 2 survives retention (kept in addition to the
    newest two) — the reference keeps best reachable by duplicating files
    (my_ray_module.py:190-201); here it's a retention policy."""
    state = _state()
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2, async_save=False)
    for step, vl in [(1, 0.9), (2, 0.4), (3, 0.7), (4, 0.6)]:
        mgr.save(step, _tree(state), metrics={"val_loss": vl})
    assert mgr.latest_step() == 4
    assert mgr.best_step() == 2
    assert mgr.all_steps() == [2, 3, 4]  # 1 pruned; best 2 retained
    meta = mgr.restore_metadata(best=True)
    assert meta["metrics"]["val_loss"] == 0.4
    # Metrics history rides in metadata (↔ val_losses list in the payload,
    # my_ray_module.py:185-186).
    assert [m["val_loss"] for m in meta["metrics_history"]] == [0.9, 0.4]
    mgr.close()


def test_history_rebuilt_on_reopen(tmp_path):
    state = _state()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, _tree(state), metrics={"val_loss": 0.9})
    mgr.save(2, _tree(state), metrics={"val_loss": 0.2})
    mgr.close()
    mgr2 = CheckpointManager(str(tmp_path), async_save=False)
    assert mgr2.latest_step() == 2
    assert mgr2.best_step() == 2
    mgr2.save(3, _tree(state), metrics={"val_loss": 0.5})
    assert mgr2.best_step() == 2
    mgr2.close()


def test_weights_only_restore_parity(tmp_path):
    """Handle-level weights-only restore: params come back; the caller's
    optimizer state stays fresh (↔ set_weights_from_checkpoint semantics,
    my_ray_module.py:253-264 + §3.2 note)."""
    state = _state(seed=1)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    ckpt = mgr.save(1, _tree(state), metrics={"val_loss": 0.1})
    mgr.close()
    handle = Checkpoint.from_json(ckpt.to_json())
    params = restore_from_handle(handle, weights_only=True)
    for a, b in zip(
        jax.tree_util.tree_leaves(state.params), jax.tree_util.tree_leaves(params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_completes(tmp_path):
    state = _state()
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(1, _tree(state), metrics={"val_loss": 1.0})
    mgr.wait_until_finished()
    assert mgr.all_steps() == [1]
    restored = mgr.restore(1)
    assert int(np.asarray(restored["step"])) == 0
    mgr.close()


def test_restore_across_topologies(tmp_path, mesh8):
    """A checkpoint whose arrays were sharded over 8 devices restores onto a
    4-device mesh with a different layout — the resharding property the
    north-star metric presumes (SURVEY.md §5 checkpoint/resume)."""
    big = np.arange(64 * 16, dtype=np.float32).reshape(64, 16)
    sharded = jax.device_put(big, dist.batch_sharding(mesh8))
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, {"w": sharded}, metrics={"val_loss": 0.3})

    mesh4 = dist.make_mesh({"data": 2, "tensor": 2}, devices=jax.devices()[:4])
    target = jax.ShapeDtypeStruct(
        (64, 16),
        jnp.float32,
        sharding=jax.sharding.NamedSharding(
            mesh4, jax.sharding.PartitionSpec("data", "tensor")
        ),
    )
    restored = mgr.restore(1, abstract_state={"w": target})
    assert restored["w"].sharding.mesh.shape["tensor"] == 2
    np.testing.assert_array_equal(np.asarray(restored["w"]), big)
    mgr.close()


def test_missing_checkpoint_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    with pytest.raises(FileNotFoundError):
        mgr.restore()
    with pytest.raises(FileNotFoundError):
        mgr.checkpoint(best=True)
    mgr.close()
    with pytest.raises(FileNotFoundError):
        Checkpoint.from_directory(str(tmp_path / "nope"))


def test_handle_json_roundtrip(tmp_path):
    state = _state()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    ckpt = mgr.save(7, _tree(state), metrics={"val_loss": 0.7})
    mgr.close()
    obj = ckpt.to_json()
    assert isinstance(obj["path"], str) and obj["metadata"]["step"] == 7
    again = Checkpoint.from_json(obj)
    with again.as_directory() as d:
        assert os.path.isdir(os.path.join(d, "state"))


def test_recycle_pool_reuses_files_without_corrupting_restores(tmp_path, mesh8):
    """Retired shard files are recycled by later saves (pages reused), and a
    restored state NEVER aliases checkpoint file pages — an in-place recycled
    overwrite must not mutate previously restored arrays."""
    sharding = dist.batch_sharding(mesh8)
    mgr = CheckpointManager(str(tmp_path), max_to_keep=1, async_save=True)
    states = [
        {"params": {"w": jax.device_put(np.full((16, 8), float(i), np.float32), sharding)}}
        for i in range(1, 5)
    ]
    for step, state in enumerate(states, start=1):
        mgr.save(step, state, metrics={"val_loss": 1.0 / step})
    mgr.wait_until_finished()

    restored = mgr.restore(
        4,
        abstract_state={
            "params": {
                "w": jax.ShapeDtypeStruct((16, 8), np.float32, sharding=sharding)
            }
        },
    )
    before = np.asarray(restored["params"]["w"]).copy()
    assert (before == 4.0).all()

    # Two more saves: retention retires step 4's files into the pool and the
    # next save overwrites them in place.
    for step in (5, 6):
        mgr.save(step, states[0], metrics={"val_loss": 1.0 / step})
    mgr.wait_until_finished()
    after = np.asarray(restored["params"]["w"])
    assert (after == before).all(), "restored state aliased recycled file pages"

    # The pool actually recycled: at most one retired-file set remains pooled,
    # and the recycle directory exists once retention has retired a step.
    assert os.path.isdir(os.path.join(str(tmp_path), ".recycle"))
    mgr.close()


def test_zero_copy_restore_is_correct_and_recycle_safe(tmp_path, mesh8):
    """zero_copy=True restores by mapping shard files (no read copy). The
    restored arrays alias file pages, so the step's files must be excluded
    from in-place recycling: later saves + retention must NOT mutate a
    previously zero-copy-restored state."""
    sharding = dist.batch_sharding(mesh8)
    mgr = CheckpointManager(str(tmp_path), max_to_keep=1, async_save=False)
    states = [
        {"params": {"w": jax.device_put(np.full((16, 8), float(i), np.float32), sharding)}}
        for i in range(1, 4)
    ]
    abstract = {
        "params": {
            "w": jax.ShapeDtypeStruct((16, 8), np.float32, sharding=sharding)
        }
    }
    mgr.save(1, states[0], metrics={"val_loss": 1.0})
    restored = mgr.restore(1, abstract_state=abstract, zero_copy=True)
    assert (np.asarray(restored["params"]["w"]) == 1.0).all()
    # Saves 2 and 3 retire step 1 (and 2) through retention; with the step
    # aliased, adopt_dir must unlink instead of pooling, so the restored
    # array's pages are never overwritten in place.
    for step in (2, 3):
        mgr.save(step, states[step - 1], metrics={"val_loss": 1.0 / step})
    mgr.wait_until_finished()
    assert (np.asarray(restored["params"]["w"]) == 1.0).all(), (
        "zero-copy restored state was mutated by recycled saves"
    )
    # Weights-only handle restore takes the same fast path.
    from tpuflow.ckpt import restore_from_handle

    params = restore_from_handle(
        mgr.checkpoint(3), weights_only=True, zero_copy=True
    )
    assert (np.asarray(params["w"]) == 3.0).all()
    mgr.close()


def test_prewarm_backs_pool_pages_and_first_save_recycles(tmp_path, mesh8):
    """Manager.prewarm pre-creates pool files sized to the retention
    footprint so even the FIRST save of a process writes onto recycled
    pages (the cold-save fix: first-touch page backing runs ~15x slower
    than steady-state writes on ballooning hypervisors), without
    corrupting the saved payload."""
    sharding = dist.batch_sharding(mesh8)
    payload = np.arange(32 * 1024 * 16, dtype=np.float32).reshape(32, 1024, 16)
    state = {"params": {"w": jax.device_put(payload, sharding)}}
    mgr = CheckpointManager(str(tmp_path), max_to_keep=1, async_save=False)
    mgr.prewarm(state)
    mgr.prewarm_wait()
    pool_dir = os.path.join(str(tmp_path), ".recycle")
    warmed = sorted(os.listdir(pool_dir))
    # 8 shards of 256 KiB each x (max_to_keep + pinned best + 1 in flight).
    assert len(warmed) == 24, warmed
    # Idempotent top-up: a repeat prewarm of the same state adds nothing.
    mgr.prewarm(state)
    mgr.prewarm_wait()
    assert sorted(os.listdir(pool_dir)) == warmed

    mgr.save(1, state, metrics={"val_loss": 1.0})
    mgr.wait_until_finished()
    restored = mgr.restore(
        1,
        abstract_state={
            "params": {
                "w": jax.ShapeDtypeStruct(
                    payload.shape, np.float32, sharding=sharding
                )
            }
        },
    )
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]), payload)
    # The first save consumed warm files (pool shrank or files were renamed
    # into the step dir).
    assert len(os.listdir(pool_dir)) < len(warmed)
    mgr.close()


def test_deferred_commit_makes_steps_visible_only_when_complete(
    tmp_path, mesh8, monkeypatch
):
    """metadata.json (step visibility) lands only after shard files are fully
    written: while the background write is stalled the step is invisible, and
    a crash in that window leaves an orphan the next manager reclaims."""
    import threading

    from tpuflow.ckpt import raw as raw_fmt

    gate = threading.Event()
    real_write_entries = raw_fmt._write_entries

    def stalled_write_entries(*args, **kwargs):
        gate.wait(timeout=30)
        return real_write_entries(*args, **kwargs)

    monkeypatch.setattr(raw_fmt, "_write_entries", stalled_write_entries)

    sharding = dist.batch_sharding(mesh8)
    state = {"w": jax.device_put(np.ones((16, 8), np.float32), sharding)}
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2, async_save=True)
    mgr.save(1, state, metrics={"val_loss": 0.5})
    step_dir = os.path.join(str(tmp_path), "step_1")
    # Save is in flight (stalled): no commit marker, step invisible.
    assert not os.path.exists(os.path.join(step_dir, "metadata.json"))
    assert mgr._all_steps() == []
    gate.set()
    assert mgr.latest_step() == 1  # waits for the commit
    assert os.path.exists(os.path.join(step_dir, "metadata.json"))
    mgr.close()


def test_crash_orphan_step_swept_on_next_manager(tmp_path, mesh8):
    """A step dir whose save never committed (no metadata.json) is reclaimed
    by the next manager construction instead of leaking storage."""
    sharding = dist.batch_sharding(mesh8)
    state = {"w": jax.device_put(np.ones((16, 8), np.float32), sharding)}
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2, async_save=False)
    mgr.save(1, state, metrics={"val_loss": 0.5})
    mgr.close()
    # Fake a crash mid-save: payload present, no commit marker.
    orphan = os.path.join(str(tmp_path), "step_9")
    os.makedirs(os.path.join(orphan, "state"))
    with open(os.path.join(orphan, "state", "leaf_00000_000.bin"), "wb") as f:
        f.write(b"\0" * 128)
    mgr2 = CheckpointManager(str(tmp_path), max_to_keep=2, async_save=False)
    assert not os.path.exists(orphan)
    assert mgr2.all_steps() == [1]
    mgr2.close()


def test_gather_host_scalar_leaf_on_nonzero_rank(monkeypatch):
    """ADVICE r1: a pure-Python scalar leaf must yield a valid manifest entry
    on processes that own no shard of it (process_index != 0)."""
    from tpuflow.ckpt import raw as raw_fmt

    monkeypatch.setattr(jax, "process_index", lambda: 1)
    entries = raw_fmt._gather_host({"epoch": 3, "w": np.ones((4,), np.float32)})
    by_path = {tuple(p): (shape, dtype, shards) for p, shape, dtype, shards in entries}
    shape, dtype, shards = by_path[("epoch",)]
    assert shape == [] and shards == []
    assert np.dtype(dtype).kind in "iu"


def test_merge_manifests_rejects_missing_fragments(tmp_path):
    """ADVICE r1: merging fewer fragments than the save's process_count must
    fail loudly instead of silently under-covering restored arrays."""
    import json

    from tpuflow.ckpt import raw as raw_fmt

    frag = {
        "format": raw_fmt.FORMAT_NAME,
        "process_count": 3,
        "leaves": [{"path": ["w"], "shape": [4], "dtype": "<f4", "shards": []}],
    }
    with open(tmp_path / "manifest.p00000.json", "w") as f:
        json.dump(frag, f)
    with open(tmp_path / "manifest.p00001.json", "w") as f:
        json.dump(frag, f)
    with pytest.raises(FileNotFoundError, match="3 processes"):
        raw_fmt.merge_manifests(str(tmp_path), visibility_timeout_s=0.2)


def test_uncommitted_handle_fails_fast(tmp_path):
    """ADVICE r1: consuming a handle to a not-yet-committed step reports the
    real reason (save not finished), not a confusing missing-manifest error."""
    step_dir = tmp_path / "step_1"
    (step_dir / "state").mkdir(parents=True)
    handle = Checkpoint(path=str(step_dir), metadata={})
    with pytest.raises(FileNotFoundError, match="not committed"):
        restore_from_handle(handle)


def test_orbax_step_visible_only_when_durable(tmp_path):
    """ADVICE r1: the Orbax branch must not write the commit marker before
    the async payload is durable — the commit is deferred to the drain, so a
    step is either invisible or fully restorable, never half-written."""
    state = _state()
    mgr = CheckpointManager(str(tmp_path), async_save=True, format="orbax")
    mgr.save(1, _tree(state), metrics={"val_loss": 1.0})
    meta = os.path.join(str(tmp_path), "step_1", "metadata.json")
    # Before the drain the step may legitimately be invisible (async write
    # in flight) — but it must never be visible-and-incomplete.
    mgr.wait_until_finished()
    assert os.path.exists(meta)
    restored = mgr.restore(1, abstract_state=_tree(state))
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["dense1"]["kernel"]),
        np.asarray(state.params["dense1"]["kernel"]),
    )
    mgr.close()


def test_restore_arena_prewarmed_buffers_are_used_and_correct(tmp_path, mesh8):
    """The restore arena hands each pre-backed buffer out exactly once, the
    restored values are identical, and exhausted sizes fall back to fresh
    allocation (raw.RestoreArena)."""
    from tpuflow.ckpt import raw

    sharding = dist.batch_sharding(mesh8, 2)
    state = {
        "w": jax.device_put(
            np.arange(16 * 64, dtype=np.float32).reshape(16, 64), sharding
        )
    }
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, state, metrics={"val_loss": 1.0})
    mgr.wait_until_finished()

    state_dir = os.path.join(str(tmp_path), "step_1", "state")
    sizes = raw.manifest_shard_sizes(state_dir)
    assert sizes and all(s > 0 for s in sizes)

    raw._ARENA.clear()
    mgr.prewarm_restore(1, background=False)
    n_buffers = sum(len(v) for v in raw._ARENA._buffers.values())
    assert n_buffers == len(sizes)

    abstract = {
        "w": jax.ShapeDtypeStruct((16, 64), np.float32, sharding=sharding)
    }
    restored = mgr.restore(1, abstract_state=abstract)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))
    # Every prewarmed buffer was consumed (transfer-only ownership).
    assert sum(len(v) for v in raw._ARENA._buffers.values()) == 0

    # Arena empty: a second restore still works (fresh allocation fallback).
    restored2 = mgr.restore(1, abstract_state=abstract)
    np.testing.assert_array_equal(np.asarray(restored2["w"]), np.asarray(state["w"]))
    mgr.close()


def test_prewarm_restore_handle_and_nonraw_noop(tmp_path):
    """prewarm_restore_handle backs buffers for a committed raw handle and is
    a silent no-op for non-checkpoint paths."""
    from tpuflow.ckpt import prewarm_restore_handle, raw

    state = _state()
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(3, _tree(state), metrics={"val_loss": 0.5})
    mgr.wait_until_finished()
    handle = mgr.checkpoint()

    raw._ARENA.clear()
    prewarm_restore_handle(handle)
    raw._ARENA.prewarm_wait()
    assert sum(len(v) for v in raw._ARENA._buffers.values()) > 0
    restored = restore_from_handle(handle, abstract_state=_tree(state))
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["dense1"]["kernel"]),
        np.asarray(state.params["dense1"]["kernel"]),
    )
    raw._ARENA.clear()

    # Bogus handle: no crash, no buffers.
    prewarm_restore_handle(Checkpoint(path=str(tmp_path / "nope"), metadata={}))
    raw._ARENA.prewarm_wait()
    assert sum(len(v) for v in raw._ARENA._buffers.values()) == 0
    mgr.close()


def test_bfloat16_leaf_dtype_roundtrips(tmp_path):
    """Manifest dtype spelling for extended types (VERDICT-class latent bug:
    np.dtype(bfloat16).str is raw void '<V2', losing the type): a bf16 leaf
    must restore as bf16 with identical bytes."""
    state = {"w": jnp.arange(64, dtype=jnp.bfloat16).reshape(8, 8) / 7.0}
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, state, metrics={"val_loss": 1.0})
    mgr.wait_until_finished()
    restored = mgr.restore(1)
    got = restored["w"]
    assert np.dtype(got.dtype) == np.dtype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(state["w"], np.float32)
    )
    mgr.close()


def test_save_dtype_halves_bytes_and_restores_to_template(tmp_path):
    """save_dtype='bfloat16': float32 leaves are written half-size, integer
    leaves stay exact, and a float32 template restores rounded-to-bf16
    values in float32."""
    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 64)).astype(np.float32)
    state = {"w": jnp.asarray(w), "step": jnp.asarray(7, jnp.int32)}

    full = CheckpointManager(str(tmp_path / "full"), async_save=False)
    full.save(1, state)
    full.wait_until_finished()
    half = CheckpointManager(
        str(tmp_path / "half"), async_save=False, save_dtype="bfloat16"
    )
    half.save(1, state)
    half.wait_until_finished()

    def payload_bytes(root):
        return sum(
            os.path.getsize(os.path.join(r, f))
            for r, _, fs in os.walk(root)
            for f in fs
            if f.endswith(".bin")
        )

    nb_full = payload_bytes(tmp_path / "full" / "step_1")
    nb_half = payload_bytes(tmp_path / "half" / "step_1")
    assert nb_half < 0.6 * nb_full  # the f32 leaf halved; the int4 is noise

    abstract = {
        "w": jax.ShapeDtypeStruct((64, 64), np.float32),
        "step": jax.ShapeDtypeStruct((), np.int32),
    }
    restored = half.restore(1, abstract_state=abstract)
    assert restored["w"].dtype == np.float32
    assert int(restored["step"]) == 7  # integers never downcast
    np.testing.assert_array_equal(
        np.asarray(restored["w"]),
        np.asarray(jnp.asarray(w).astype(jnp.bfloat16), np.float32),
    )
    assert half.restore_metadata(1)["save_dtype"] == "bfloat16"
    full.close()
    half.close()


def test_concurrent_restores_are_serialized_and_correct(tmp_path):
    """Two threads restoring DIFFERENT checkpoints concurrently (with a
    prewarm for one issued mid-flight) must both get exact bytes — the
    process-wide restore lock + landed-only arena cleanup (ADVICE r2 #4)
    protect the global RestoreArena hand-off."""
    import threading

    import numpy as np

    from tpuflow.ckpt import CheckpointManager

    rng = np.random.default_rng(7)
    payloads, mgrs = [], []
    for i in range(2):
        state = {"w": rng.standard_normal((64, 1024)).astype(np.float32)}
        mgr = CheckpointManager(str(tmp_path / f"ck{i}"), max_to_keep=1)
        mgr.save(1, state)
        mgr.wait_until_finished()
        payloads.append(state)
        mgrs.append(mgr)

    results: dict[int, np.ndarray] = {}
    errors: list[BaseException] = []

    def restore(i: int):
        try:
            mgrs[i].prewarm_restore(1, background=True)
            out = mgrs[i].restore(1)
            results[i] = np.asarray(out["w"])
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=restore, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    for i in range(2):
        np.testing.assert_array_equal(results[i], payloads[i]["w"])
    for m in mgrs:
        m.close()
    # Terminal reclamation: nothing left pinned in the process arena.
    from tpuflow.ckpt import raw as raw_fmt

    assert raw_fmt._ARENA._buffers == {}


def test_fuzz_random_pytrees_roundtrip_bit_exact(tmp_path, mesh8):
    """Property fuzz: random nested pytrees — mixed dtypes (f32/bf16/f16/
    i32/u8/bool), shapes from scalar to 3-D, replicated / batch-sharded /
    host-numpy leaves, nested dicts and lists — must round-trip BIT-exact
    through save + cross-sharding restore. 12 seeded trees."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuflow import dist
    from tpuflow.ckpt import CheckpointManager

    dtypes = [np.float32, jnp.bfloat16, np.float16, np.int32, np.uint8, bool]

    def rand_leaf(rng, i):
        dt = dtypes[int(rng.integers(len(dtypes)))]
        ndim = int(rng.integers(0, 4))
        # Leading dim divisible by 8 so batch sharding is always legal.
        shape = tuple(
            8 * int(rng.integers(1, 3)) if d == 0 else int(rng.integers(1, 9))
            for d in range(ndim)
        )
        raw = rng.integers(0, 2, size=shape) if dt is bool else (
            rng.standard_normal(shape) * 10
        )
        arr = np.asarray(raw).astype(dt)
        kind = int(rng.integers(3)) if ndim else 2
        if kind == 0:  # batch-sharded device array
            return jax.device_put(arr, dist.batch_sharding(mesh8, ndim))
        if kind == 1:  # replicated device array
            return jax.device_put(arr, dist.replicated(mesh8))
        return arr  # host numpy

    def rand_tree(rng, depth=0):
        n = int(rng.integers(1, 4))
        out = {}
        for i in range(n):
            if depth < 2 and rng.random() < 0.3:
                out[f"d{i}"] = rand_tree(rng, depth + 1)
            elif rng.random() < 0.2:
                out[f"l{i}"] = [rand_leaf(rng, i), rand_leaf(rng, i)]
            else:
                out[f"w{i}"] = rand_leaf(rng, i)
        return out

    for seed in range(12):
        rng = np.random.default_rng(seed)
        tree = rand_tree(rng)
        d = str(tmp_path / f"fz{seed}")
        mgr = CheckpointManager(d, max_to_keep=1)
        with mesh8:
            mgr.save(1, tree)
            mgr.wait_until_finished()
            # Restore against an abstract template with DIFFERENT
            # placement (everything replicated): exercises resharding on
            # every sharded leaf.
            abstract = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    np.shape(a),
                    a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype,
                    sharding=dist.replicated(mesh8),
                ),
                tree,
            )
            restored = mgr.restore(1, abstract_state=abstract)
        mgr.close()
        flat_w, _ = jax.tree_util.tree_flatten(tree)
        flat_r, _ = jax.tree_util.tree_flatten(restored)
        assert len(flat_w) == len(flat_r)
        for w, r in zip(flat_w, flat_r):
            wa, ra = np.asarray(w), np.asarray(r)
            assert wa.dtype == ra.dtype and wa.shape == ra.shape, (
                seed, wa.dtype, ra.dtype, wa.shape, ra.shape
            )
            assert wa.tobytes() == ra.tobytes(), (seed, wa.dtype, wa.shape)


def test_prewarm_parks_on_starved_box(tmp_path, monkeypatch):
    """With no spare core (TPUFLOW_PREWARM_THREADS=0), background prewarm
    must not spawn work — it parks, runs only under an explicit blocking
    wait, and is dropped by cancel/clear (an early capture's prewarm_overlap
    measured the old always-spawn behavior actively harmful: -16 s)."""
    from tpuflow.ckpt.raw import RecyclePool, RestoreArena

    monkeypatch.setenv("TPUFLOW_PREWARM_THREADS", "0")
    size = 1 << 20
    pool = RecyclePool(str(tmp_path / "pool"))
    pool.prewarm([size, size])
    assert not pool._warm_threads  # no thread: parked
    assert pool.take(size) is None  # nothing materialized
    pool.prewarm_wait()  # blocking caller runs parked work itself
    assert pool.take(size) is not None
    assert pool.take(size) is not None

    # cancel_prewarm drops parked work (and releases its promises so a
    # later prewarm can re-book the sizes).
    pool2 = RecyclePool(str(tmp_path / "pool2"))
    pool2.prewarm([size])
    pool2.cancel_prewarm()
    pool2.prewarm_wait()
    assert pool2.take(size) is None
    assert not pool2._warm_promised
    pool2.prewarm([size])  # re-book works after the cancel
    pool2.prewarm_wait()
    assert pool2.take(size) is not None

    arena = RestoreArena()
    try:
        arena.prewarm([size])
        assert arena.take(size) is None  # parked
        arena.prewarm_wait()
        assert arena.take(size) is not None
        arena.prewarm([size])
        arena.clear()  # drops parked work without executing it
        arena.prewarm_wait()
        assert arena.take(size) is None
    finally:
        arena.clear()


def test_prewarm_background_when_spare_cores(tmp_path, monkeypatch):
    """With spare cores the background thread path still materializes the
    pool without the caller blocking for it."""
    from tpuflow.ckpt.raw import RecyclePool, RestoreArena

    monkeypatch.setenv("TPUFLOW_PREWARM_THREADS", "1")
    size = 1 << 20
    pool = RecyclePool(str(tmp_path / "pool"))
    pool.prewarm([size])
    pool.prewarm_wait()  # join the real background thread
    assert pool.take(size) is not None

    arena = RestoreArena()
    try:
        arena.prewarm([size])
        arena.prewarm_wait()
        assert arena.take(size) is not None
    finally:
        arena.clear()


# ===================================================== durability (ISSUE 5)
@pytest.fixture
def obs_events(tmp_path):
    """Route telemetry into a temp dir; yields a flush-and-read closure."""
    from tpuflow import obs

    d = str(tmp_path / "obsdir")
    obs.configure(d, proc=0)

    def read():
        obs.flush()
        events = []
        for name in sorted(os.listdir(d)):
            if name.startswith("events.p"):
                events += obs.read_events(os.path.join(d, name))
        return events

    yield read
    obs.configure(None)


@pytest.fixture
def clean_faults(monkeypatch):
    from tpuflow.testing import faults

    monkeypatch.delenv("TPUFLOW_FAULT", raising=False)
    faults.reset()
    yield faults
    faults.reset()


def _flip_byte_in(path: str) -> None:
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))


def test_retry_io_transient_backoff_then_success(obs_events):
    """Transient OSErrors are retried with growing jittered backoff and
    ckpt.io_retry telemetry; the wrapped op's result comes through."""
    import errno

    from tpuflow.ckpt import raw

    calls = {"n": 0}
    sleeps: list[float] = []

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError(errno.EIO, "blip")
        return 42

    assert raw.retry_io(flaky, op="t", path="/x/y.bin", _sleep=sleeps.append) == 42
    assert calls["n"] == 3 and len(sleeps) == 2
    # Exponential envelope with 50-100% jitter on a 0.05 base.
    assert 0.025 <= sleeps[0] <= 0.05 and 0.05 <= sleeps[1] <= 0.1
    retries = [e for e in obs_events() if e["name"] == "ckpt.io_retry"]
    assert [e["attempt"] for e in retries] == [1, 2]
    assert retries[0]["op"] == "t" and retries[0]["path"] == "y.bin"


def test_retry_io_permanent_and_structural_errors(obs_events):
    """Permanent errnos raise CheckpointIOError on the FIRST attempt
    (ckpt.io_error recorded); structural absence (ENOENT) re-raises
    unchanged so callers keep their semantics."""
    import errno

    from tpuflow.ckpt import raw

    sleeps: list[float] = []

    def denied():
        raise OSError(errno.EACCES, "nope")

    with pytest.raises(raw.CheckpointIOError):
        raw.retry_io(denied, op="t", _sleep=sleeps.append)
    assert not sleeps  # no retry of a permanent error

    def missing():
        raise FileNotFoundError(errno.ENOENT, "gone")

    with pytest.raises(FileNotFoundError) as ei:
        raw.retry_io(missing, op="t", _sleep=sleeps.append)
    assert not isinstance(ei.value, raw.CheckpointIOError)
    errs = [e for e in obs_events() if e["name"] == "ckpt.io_error"]
    assert len(errs) == 1 and errs[0]["transient"] is False


def test_retry_io_exhaustion_raises(monkeypatch, obs_events):
    import errno

    from tpuflow.ckpt import raw

    monkeypatch.setenv("TPUFLOW_CKPT_IO_RETRIES", "2")
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise OSError(errno.EIO, "down")

    with pytest.raises(raw.CheckpointIOError, match="3 attempts"):
        raw.retry_io(always, op="t", _sleep=lambda s: None)
    assert calls["n"] == 3
    errs = [e for e in obs_events() if e["name"] == "ckpt.io_error"]
    assert errs and errs[0]["transient"] is True


def test_flaky_io_save_absorbed_by_retries(
    tmp_path, monkeypatch, clean_faults, obs_events
):
    """ckpt_io_flaky:p2 under the default retry budget: every save/restore
    op blips twice and succeeds — the checkpoint round-trips bit-exact
    with ckpt.io_retry evidence, nothing fails."""
    monkeypatch.setenv("TPUFLOW_CKPT_IO_BACKOFF_S", "0.001")
    monkeypatch.setenv("TPUFLOW_FAULT", "ckpt_io_flaky:p2")
    w = np.arange(2048, dtype=np.float32)
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    mgr.save(1, {"w": w}, metrics={"val_loss": 1.0})
    out = mgr.restore(1)
    np.testing.assert_array_equal(out["w"], w)
    mgr.close()
    retries = [e for e in obs_events() if e["name"] == "ckpt.io_retry"]
    assert {e["op"] for e in retries} >= {"write_shard", "read_shard"}


def test_save_exhausting_retries_fails_step_cleanly(
    tmp_path, monkeypatch, clean_faults, obs_events
):
    """THE tentpole contract: a save whose retries exhaust fails THAT
    step's save — staging reclaimed, history entry dropped,
    ckpt.save_failed recorded — and the manager keeps working; it never
    raises into the training loop."""
    monkeypatch.setenv("TPUFLOW_CKPT_IO_RETRIES", "0")
    monkeypatch.setenv("TPUFLOW_FAULT", "ckpt_io_flaky:p9")
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    mgr.save(1, {"w": np.ones(256, np.float32)}, metrics={"val_loss": 1.0})
    assert mgr.all_steps() == []  # the save failed, cleanly
    assert mgr._metrics_history == []  # the step never existed
    assert not [
        n for n in os.listdir(tmp_path / "ck") if n.endswith(".tmp")
    ], "failed save leaked staging"
    # Storage recovers -> the next save commits normally.
    monkeypatch.delenv("TPUFLOW_FAULT")
    clean_faults.reset()
    mgr.save(2, {"w": np.full(256, 2.0, np.float32)}, metrics={"val_loss": 0.5})
    assert mgr.all_steps() == [2]
    np.testing.assert_array_equal(
        mgr.restore(2)["w"], np.full(256, 2.0, np.float32)
    )
    mgr.close()
    events = obs_events()
    failed = [e for e in events if e["name"] == "ckpt.save_failed"]
    assert failed and failed[0]["step"] == 1
    assert any(e["name"] == "ckpt.io_error" for e in events)


def test_partial_commit_staged_dir_gc_on_next_manager(
    tmp_path, monkeypatch, clean_faults, obs_events
):
    """A writer killed between payload and commit (ckpt_partial_commit)
    leaves only an invisible step_K.tmp staging dir; the next manager
    garbage-collects it (ckpt.gc) — it can never be mistaken for a
    restorable step."""
    monkeypatch.setenv("TPUFLOW_FAULT", "ckpt_partial_commit")
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    mgr.save(1, {"w": np.ones(256, np.float32)}, metrics={"val_loss": 1.0})
    assert mgr.all_steps() == []
    staged = [n for n in os.listdir(tmp_path / "ck") if n.endswith(".tmp")]
    assert staged == ["step_1.tmp"]
    assert not os.path.exists(tmp_path / "ck" / "step_1")
    mgr.close()
    monkeypatch.delenv("TPUFLOW_FAULT")
    clean_faults.reset()
    mgr2 = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    assert not os.path.exists(tmp_path / "ck" / "step_1.tmp")
    assert mgr2.all_steps() == []
    mgr2.close()
    gc = [e for e in obs_events() if e["name"] == "ckpt.gc"]
    assert gc and "step_1.tmp" in gc[0]["dirs"]


def test_local_tier_save_upload_restore_and_retention(
    tmp_path, monkeypatch, obs_events
):
    """With TPUFLOW_CKPT_LOCAL_DIR set, saves commit locally and upload to
    the persistent dir (ckpt.upload span); restores prefer the local copy
    (ckpt.restore_tier=local); TPUFLOW_CKPT_LOCAL_KEEP bounds local disk
    with oldest-first eviction while the persistent tier keeps its own
    retention."""
    monkeypatch.setenv("TPUFLOW_CKPT_LOCAL_DIR", str(tmp_path / "local"))
    monkeypatch.setenv("TPUFLOW_CKPT_LOCAL_KEEP", "2")
    mgr = CheckpointManager(
        str(tmp_path / "ck"), async_save=False, max_to_keep=None
    )
    assert mgr.local_dir is not None
    for step in (1, 2, 3):
        mgr.save(
            step,
            {"w": np.full(512, float(step), np.float32)},
            metrics={"val_loss": 1.0 / step},
        )
    # Persistent keeps everything (max_to_keep=None); local keeps newest 2.
    assert mgr._committed_in(mgr.directory) == [1, 2, 3]
    assert mgr._committed_in(mgr.local_dir) == [2, 3]
    out = mgr.restore(3)
    np.testing.assert_array_equal(out["w"], np.full(512, 3.0, np.float32))
    # Step 1 was evicted locally: restore serves it from persistent.
    np.testing.assert_array_equal(
        mgr.restore(1)["w"], np.full(512, 1.0, np.float32)
    )
    mgr.close()
    events = obs_events()
    uploads = [e for e in events if e["name"] == "ckpt.upload"]
    assert [e["step"] for e in uploads] == [1, 2, 3]
    assert all(e["ok"] for e in uploads)
    tiers = {
        e["step"]: e["tier"] for e in events if e["name"] == "ckpt.restore_tier"
    }
    assert tiers == {3: "local", 1: "persistent"}


def test_restore_fallback_ladder_end_to_end(
    tmp_path, monkeypatch, obs_events
):
    """Satellite: the full ladder — crc-corrupt local copy → valid
    persistent copy → corrupt persistent copy → previous committed step —
    with ckpt.verify / ckpt.corrupt / ckpt.restore_tier evidence at each
    hop, and a hard CorruptShardError only when nothing valid remains."""
    import glob as glob_mod

    from tpuflow.ckpt import CorruptShardError

    monkeypatch.setenv("TPUFLOW_CKPT_LOCAL_DIR", str(tmp_path / "local"))
    mgr = CheckpointManager(
        str(tmp_path / "ck"), async_save=False, max_to_keep=None
    )
    for step in (1, 2):
        mgr.save(
            step,
            {"w": np.full(1024, float(step), np.float32)},
            metrics={"val_loss": 1.0 / step},
        )

    def shard_of(root, step):
        (p,) = glob_mod.glob(
            os.path.join(root, f"step_{step}", "state", "*.bin")
        )
        return p

    # Hop 1: corrupt the LOCAL copy of step 2 -> verify flags it, restore
    # falls through to the valid persistent copy.
    _flip_byte_in(shard_of(mgr.local_dir, 2))
    assert mgr.verify_step(2) is False  # audits the tier a restore reads first
    out = mgr.restore(2)
    np.testing.assert_array_equal(out["w"], np.full(1024, 2.0, np.float32))
    # Hop 2: corrupt the persistent copy too -> restore(2) lands on the
    # previous committed step (1), serving its local copy.
    _flip_byte_in(shard_of(mgr.directory, 2))
    out = mgr.restore(2)
    np.testing.assert_array_equal(out["w"], np.full(1024, 1.0, np.float32))
    # Hop 3: with every copy of every step corrupt, the error propagates.
    _flip_byte_in(shard_of(mgr.local_dir, 1))
    _flip_byte_in(shard_of(mgr.directory, 1))
    with pytest.raises(CorruptShardError):
        mgr.restore(2)
    mgr.close()

    events = obs_events()
    verifies = [e for e in events if e["name"] == "ckpt.verify"]
    assert verifies and verifies[0]["step"] == 2 and not verifies[0]["ok"]
    corrupt_hops = [
        (e["step"], e.get("tier"))
        for e in events
        if e["name"] == "ckpt.corrupt" and "error" in e
    ]
    # First restore: local(2) rejected; second: local(2) + persistent(2);
    # third: all four copies rejected.
    assert corrupt_hops[0] == (2, "local")
    assert (2, "persistent") in corrupt_hops
    assert (1, "local") in corrupt_hops and (1, "persistent") in corrupt_hops
    served = [
        (e["step"], e["tier"])
        for e in events
        if e["name"] == "ckpt.restore_tier"
    ]
    assert served == [(2, "persistent"), (1, "local")]


def test_emergency_save_is_local_only_and_resumable(
    tmp_path, monkeypatch, obs_events
):
    """emergency_save commits synchronously on the local tier WITHOUT the
    persistent upload; a new manager (the requeued attempt) resumes from
    the emergency step with continuous embedded history."""
    monkeypatch.setenv("TPUFLOW_CKPT_LOCAL_DIR", str(tmp_path / "local"))
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    mgr.save(1, {"w": np.full(256, 1.0, np.float32)}, metrics={"val_loss": 1.0})
    mgr.emergency_save(
        2,
        {"w": np.full(256, 2.0, np.float32)},
        data_state={"epoch": 0, "batch_index": 2, "seed": 0},
    )
    assert mgr.all_steps() == [1, 2]
    assert mgr._committed_in(mgr.directory) == [1]  # upload skipped
    assert mgr._committed_in(mgr.local_dir) == [1, 2]
    mgr.close()
    # The requeued attempt: same persistent dir + same local root.
    mgr2 = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    assert mgr2.latest_step() == 2
    assert [m["step"] for m in mgr2._metrics_history] == [1, 2]
    out = mgr2.restore()
    np.testing.assert_array_equal(out["w"], np.full(256, 2.0, np.float32))
    assert mgr2.restore_metadata(2)["data_state"]["batch_index"] == 2
    mgr2.close()
    events = obs_events()
    em = [e for e in events if e["name"] == "ckpt.emergency_save"]
    assert em and em[0]["step"] == 2 and em[0]["tier"] == "local" and em[0]["ok"]
    assert ("ckpt.restore_tier", "local") in [
        (e["name"], e.get("tier")) for e in events
    ]


def test_local_tier_startup_sweep_bounds_disk(tmp_path, monkeypatch, obs_events):
    """Satellite: manager startup sweeps stale local staging dirs from
    killed attempts AND evicts committed local steps beyond
    TPUFLOW_CKPT_LOCAL_KEEP — requeue loops cannot fill node disk."""
    monkeypatch.setenv("TPUFLOW_CKPT_LOCAL_DIR", str(tmp_path / "local"))
    monkeypatch.setenv("TPUFLOW_CKPT_LOCAL_KEEP", "2")
    mgr = CheckpointManager(
        str(tmp_path / "ck"), async_save=False, max_to_keep=None
    )
    for step in (1, 2):
        mgr.save(step, {"w": np.ones(128, np.float32)}, metrics={})
    mgr.close()
    # A killed attempt's leftovers: stale staging + an extra local step dir
    # beyond retention (hand-made, oldest).
    os.makedirs(os.path.join(mgr.local_dir, "step_9.tmp", "state"))
    stale = os.path.join(mgr.local_dir, "step_0")
    os.makedirs(os.path.join(stale, "state"))
    with open(os.path.join(stale, "metadata.json"), "w") as f:
        f.write('{"step": 0, "metrics": {}}')
    mgr2 = CheckpointManager(
        str(tmp_path / "ck"), async_save=False, max_to_keep=None
    )
    assert not os.path.exists(os.path.join(mgr2.local_dir, "step_9.tmp"))
    assert not os.path.exists(stale)  # 0 evicted: keep newest 2 = {1, 2}
    assert mgr2._committed_in(mgr2.local_dir) == [1, 2]
    mgr2.close()
    gc = [e for e in obs_events() if e["name"] == "ckpt.gc"]
    assert gc and {"local:step_9.tmp", "local:step_0"} <= set(gc[0]["dirs"])


def test_handle_alt_paths_serve_surviving_tier(tmp_path, monkeypatch):
    """A manager handle carries the local copy as an alternate path:
    as_directory serves the persistent dir while it exists and falls to
    the local tier when it is gone; alt_paths survive the JSON round-trip."""
    import shutil

    from tpuflow.ckpt import restore_from_handle

    monkeypatch.setenv("TPUFLOW_CKPT_LOCAL_DIR", str(tmp_path / "local"))
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    mgr.save(1, {"w": np.full(64, 5.0, np.float32)}, metrics={})
    handle = mgr.checkpoint()
    mgr.close()
    assert handle.path.startswith(str(tmp_path / "ck"))
    assert handle.alt_paths and handle.alt_paths[0].startswith(
        str(tmp_path / "local")
    )
    again = Checkpoint.from_json(handle.to_json())
    assert again.alt_paths == handle.alt_paths
    shutil.rmtree(handle.path)  # persistent tier lost
    out = restore_from_handle(again)
    np.testing.assert_array_equal(out["w"], np.full(64, 5.0, np.float32))


def test_upload_stall_and_failure_keep_step_durable_locally(
    tmp_path, monkeypatch, clean_faults, obs_events
):
    """An upload that stalls then fails for good (copytree target made
    unwritable via fault-free monkeypatching) leaves the step committed
    on the local tier: ckpt.upload records ok=False, nothing raises, and
    the restore serves locally."""
    import shutil as shutil_mod

    monkeypatch.setenv("TPUFLOW_CKPT_LOCAL_DIR", str(tmp_path / "local"))
    monkeypatch.setenv("TPUFLOW_CKPT_IO_RETRIES", "1")
    monkeypatch.setenv("TPUFLOW_CKPT_IO_BACKOFF_S", "0.001")
    monkeypatch.setenv("TPUFLOW_FAULT", "upload_stall:0.05")
    import errno as errno_mod

    real_copytree = shutil_mod.copytree
    calls = {"n": 0}

    def failing_copytree(src, dst, **kw):
        calls["n"] += 1
        raise OSError(errno_mod.EIO, "shared fs down")

    monkeypatch.setattr(shutil_mod, "copytree", failing_copytree)
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    mgr.save(1, {"w": np.full(64, 7.0, np.float32)}, metrics={})
    assert calls["n"] == 2  # initial + one retry
    assert mgr._committed_in(mgr.local_dir) == [1]
    assert mgr._committed_in(mgr.directory) == []
    np.testing.assert_array_equal(
        mgr.restore(1)["w"], np.full(64, 7.0, np.float32)
    )
    monkeypatch.setattr(shutil_mod, "copytree", real_copytree)
    mgr.close()
    uploads = [e for e in obs_events() if e["name"] == "ckpt.upload"]
    assert uploads and uploads[0]["ok"] is False
    assert uploads[0]["dur_s"] >= 0.05  # the injected stall was absorbed


def test_prewarm_retries_through_io_wrapper(
    tmp_path, monkeypatch, clean_faults, obs_events
):
    """Satellite: a transient error during pool prewarm is retried through
    retry_io (ckpt.io_retry emitted) instead of silently leaving the warm
    file absent."""
    from tpuflow.ckpt.raw import RecyclePool

    monkeypatch.setenv("TPUFLOW_PREWARM_THREADS", "0")
    monkeypatch.setenv("TPUFLOW_CKPT_IO_BACKOFF_S", "0.001")
    monkeypatch.setenv("TPUFLOW_FAULT", "ckpt_io_flaky:p1")
    size = 1 << 20
    pool = RecyclePool(str(tmp_path / "pool"))
    pool.prewarm([size])
    pool.prewarm_wait()  # parked work runs here, through the wrapper
    assert pool.take(size) is not None, "warm file silently absent"
    retries = [e for e in obs_events() if e["name"] == "ckpt.io_retry"]
    assert retries and retries[0]["op"] == "prewarm"


def test_data_state_persists_in_metadata(tmp_path):
    """save(data_state=...) rides the step metadata for deterministic
    mid-epoch resume; absent when not passed."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(
        1,
        {"w": np.ones(16, np.float32)},
        metrics={"val_loss": 1.0},
        data_state={"epoch": 3, "batch_index": 7, "seed": 11},
    )
    mgr.save(2, {"w": np.ones(16, np.float32)}, metrics={"val_loss": 0.9})
    assert mgr.restore_metadata(1)["data_state"] == {
        "epoch": 3, "batch_index": 7, "seed": 11,
    }
    assert "data_state" not in mgr.restore_metadata(2)
    mgr.close()


def test_arena_abandon_discards_in_flight(monkeypatch):
    """abandon() (manager.close's terminal reclamation) must drop landed
    + parked buffers AND make an in-flight background prewarm discard its
    remaining work — without joining it (a multi-GB page-touch must never
    block an unrelated manager's close)."""
    import threading

    from tpuflow.ckpt import raw as raw_fmt

    monkeypatch.setenv("TPUFLOW_PREWARM_THREADS", "1")
    arena = raw_fmt.RestoreArena()
    size = 1 << 20
    gate = threading.Event()
    orig = raw_fmt._native.aligned_empty

    def slow_alloc(n):
        gate.wait(5)  # hold the background thread mid-_back
        return orig(n)

    try:
        monkeypatch.setattr(raw_fmt._native, "aligned_empty", slow_alloc)
        arena.prewarm([size])          # background thread blocks in alloc
        arena.abandon()                # returns immediately, no join
        gate.set()                     # thread resumes, must discard
        arena.prewarm_wait()
        assert arena.take(size) is None  # nothing landed post-abandon
        # The arena recovers: a fresh prewarm on the new generation lands.
        arena.prewarm([size])
        arena.prewarm_wait()
        assert arena.take(size) is not None
    finally:
        gate.set()
        arena.clear()
