"""Per-host sharded batch loader with seeded per-epoch reshuffle.

TPU-native replacement for ``DataLoader`` + ``DistributedSampler`` as wrapped
by ``ray.train.torch.prepare_data_loader`` (reference my_ray_module.py:70-76,
128-129): each data-parallel shard sees 1/world of the data, the per-epoch
reshuffle is a permutation seeded by (seed, epoch) — the ``set_epoch``
semantics of my_ray_module.py:149-151 — and train batches are fixed-shape
(drop_last) so the jitted step never recompiles. Validation keeps the ragged
tail by padding + masking (consumed by make_eval_step's ``mask``).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

from tpuflow.data.datasets import Split
from tpuflow.utils import knobs


def _take(arr: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Batch row gather; float32 image rows go through the multithreaded
    native copy (tpuflow/_native/io.cpp dataio_gather_f32)."""
    if arr.dtype == np.float32 and arr.ndim >= 2:
        from tpuflow import _native

        return _native.gather_f32(arr, idx)
    return arr[idx]


@dataclasses.dataclass
class ShardedLoader:
    """Iterate fixed-shape batches of one shard of a Split.

    ``num_shards``/``shard_index`` default to a single shard; the trainer sets
    them to (data-parallel world, this worker's rank). When the shard sizes
    are uneven the permutation is wrap-padded so every shard sees the same
    number of batches — the same trick DistributedSampler uses, which keeps
    the collective-running gang in lockstep.
    """

    split: Split
    batch_size: int
    shuffle: bool = False
    seed: int = 0
    shard_index: int = 0
    num_shards: int = 1
    drop_last: bool = True
    pad_tail: bool = False  # emit a final padded+masked batch (eval mode)
    # Cap on batches per epoch (None = all). The per-epoch permutation still
    # ranges over the WHOLE split, so successive epochs cover different
    # subsets — bounding epoch length without pinning training to a prefix.
    max_batches: int | None = None

    def __post_init__(self):
        if not 0 <= self.shard_index < self.num_shards:
            raise ValueError(
                f"shard_index {self.shard_index} out of range for "
                f"{self.num_shards} shards"
            )
        self._epoch = 0
        self._skip_next = 0

    def set_epoch(self, epoch: int) -> None:
        """Reseed the shuffle for a new epoch (parity: sampler.set_epoch,
        reference my_ray_module.py:149-151)."""
        self._epoch = epoch

    def skip_batches(self, n: int) -> None:
        """Skip the first ``n`` batches of the NEXT iteration (one-shot).

        Deterministic mid-epoch resume (ISSUE 5): the per-epoch
        permutation is a pure function of (seed, epoch), so after a
        restore whose checkpoint metadata recorded the loader cursor
        (epoch, batches consumed, seed), skipping exactly the consumed
        batches replays the epoch's REMAINDER bit-for-bit — no batch is
        trained twice and none is dropped. The skip applies once: the
        following epochs iterate from their head as usual.
        """
        self._skip_next = max(int(n), 0)

    def reshard(self, shard_index: int, num_shards: int) -> None:
        """Re-key this loader to a resized data-parallel world (ISSUE 7:
        elastic mesh shrink/grow re-forms the gang mid-run).

        Only the stride slice over the per-epoch permutation changes —
        the permutation itself is a pure function of ``(seed, epoch)``,
        so ``data_state`` continuity composes with the resize: feeding
        the cursor recorded by the OLD world into ``set_epoch`` +
        ``skip_batches`` resumes the epoch DETERMINISTICALLY under the
        new shard map (same permutation, new stride). Row-level
        continuity across the resize boundary is approximate — the old
        and new strides interleave rows differently — but epoch and
        step accounting stay exact, which is what the train loops key
        on.
        """
        if not 0 <= shard_index < num_shards:
            raise ValueError(
                f"shard_index {shard_index} out of range for "
                f"{num_shards} shards"
            )
        self.shard_index = shard_index
        self.num_shards = num_shards

    def state_dict(self, batches_consumed: int) -> dict:
        """The loader cursor a checkpoint should persist for deterministic
        mid-epoch resume: pair with ``set_epoch`` + ``skip_batches`` on
        the restoring side (CheckpointManager.save(data_state=...))."""
        return {
            "epoch": int(self._epoch),
            "batch_index": int(batches_consumed),
            "seed": int(self.seed),
        }

    def _indices(self) -> np.ndarray:
        n = len(self.split)
        if self.shuffle:
            order = np.random.default_rng(
                (self.seed, self._epoch)
            ).permutation(n)
        else:
            order = np.arange(n)
        if self.num_shards > 1:
            per = -(-n // self.num_shards)  # ceil
            padded = np.concatenate([order, order[: per * self.num_shards - n]])
            order = padded[self.shard_index :: self.num_shards]
        return order

    def __len__(self) -> int:
        n = len(self._indices())
        if self.drop_last and not self.pad_tail:
            count = n // self.batch_size
        else:
            count = -(-n // self.batch_size)
        if self.max_batches is not None:
            count = min(count, self.max_batches)
        return count

    def __iter__(self) -> Iterator[dict]:
        order = self._indices()
        if self.max_batches is not None:
            order = order[: self.max_batches * self.batch_size]
        bs = self.batch_size
        skip, self._skip_next = self._skip_next, 0
        if skip:
            # Mid-epoch resume: drop exactly the already-consumed prefix;
            # the permutation above is identical for the same (seed,
            # epoch), so what remains is the epoch's exact tail.
            order = order[skip * bs :]
        n_full = len(order) // bs
        for b in range(n_full):
            idx = order[b * bs : (b + 1) * bs]
            yield {
                "x": _take(self.split.images, idx),
                "y": self.split.labels[idx],
                "mask": np.ones(bs, np.float32),
            }
        tail = len(order) - n_full * bs
        if tail and self.pad_tail:
            idx = order[n_full * bs :]
            pad = bs - tail
            pad_idx = np.concatenate([idx, np.repeat(idx[-1:], pad)])
            mask = np.concatenate(
                [np.ones(tail, np.float32), np.zeros(pad, np.float32)]
            )
            yield {
                "x": _take(self.split.images, pad_idx),
                "y": self.split.labels[pad_idx],
                "mask": mask,
            }
        elif tail and not self.drop_last:
            idx = order[n_full * bs :]
            yield {
                "x": _take(self.split.images, idx),
                "y": self.split.labels[idx],
                "mask": np.ones(tail, np.float32),
            }


def prefetch_depth(default: int = 2) -> int:
    """Resolve the device-prefetch depth: ``TPUFLOW_PREFETCH_DEPTH``
    beats ``default``; values <= 0 DISABLE prefetch (the loops then
    assemble + place batches inline, no thread spawned — the overhead
    pin in tests/test_data.py holds the disabled path to one int check
    per call). A malformed value falls back to ``default``."""
    import os

    env = knobs.raw("TPUFLOW_PREFETCH_DEPTH")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    return default


def prefetch_to_device(loader, mesh, *, depth: int | None = None, keys=None,
                       place=None):
    """Pipeline batch assembly + host→device placement against compute.

    A background thread assembles batches (the threaded C++ gather) and
    places them on the mesh (``dist.shard_batch``, or the caller's
    ``place``) up to ``depth`` ahead, while the main thread's jitted
    steps run — double-buffering the host side of the input pipeline the
    way ``prepare_data_loader``'s device iterator does in the reference
    stack (my_ray_module.py:128-129). Safe under multi-host: placement
    is per-process local (no collectives).

    ``depth``: buffered batches; ``None`` resolves via
    :func:`prefetch_depth` (``TPUFLOW_PREFETCH_DEPTH``, default 2).
    Depth <= 0 disables the pipeline entirely: batches are assembled and
    placed inline on the consumer thread — no thread, no queue — which
    is the knob for platforms where a background device_put is unwanted.
    ``keys``: optional subset of batch entries to keep (e.g. ("x", "y")).
    ``place``: optional ``batch -> placed_batch`` callable run on the
    prefetch thread (default ``dist.shard_batch`` onto ``mesh``) — the
    train legs pass their own sharded ``device_put`` so the placement
    matches the step's batch sharding exactly.

    Telemetry: per-batch ``data.batch_wait_s`` histogram plus the
    ``data.host_wait_s`` gauge (the time the consumer actually blocked —
    ~0 on every prefetch hit is the "input pipeline is off the critical
    path" evidence), and ``data.prefetch_hit``/``miss`` counters.
    """
    from tpuflow import dist, obs

    if place is None:
        place = lambda batch: dist.shard_batch(batch, mesh)  # noqa: E731
    if depth is None:
        depth = prefetch_depth()
    if depth <= 0:
        # Disabled path: inline assembly + placement, no thread spawned.
        # Kept deliberately bare — one generator frame over the loader —
        # so disabling prefetch never costs more than the work it defers.
        def _inline():
            import time

            obs_on = obs.enabled()

            def _placed(batch):
                if keys is not None:
                    batch = {k: batch[k] for k in keys}
                return place(batch)

            for batch in loader:
                if not obs_on:
                    yield _placed(batch)
                    continue
                # Inline, the consumer's wait is the placement itself.
                t0 = time.monotonic()
                with obs.span("data.wait", hit=False):
                    placed = _placed(batch)
                wait = time.monotonic() - t0
                obs.histogram("data.batch_wait_s", wait)
                obs.gauge("data.host_wait_s", wait)
                obs.counter("data.prefetch_miss")
                yield placed

        return _inline()
    return _prefetch_threaded(loader, place, depth, keys)


def _prefetch_threaded(loader, place, depth: int, keys):
    import queue
    import threading

    q: queue.Queue = queue.Queue(maxsize=max(1, depth))
    done = object()
    stop = threading.Event()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _worker():
        try:
            for batch in loader:
                if keys is not None:
                    batch = {k: batch[k] for k in keys}
                if not _put(place(batch)):
                    return  # consumer went away (early break)
            _put(done)
        except BaseException as e:  # surfaced on the consuming thread
            _put(e)

    thread = threading.Thread(target=_worker, daemon=True)
    thread.start()
    # Telemetry (tpuflow.obs): batch-wait vs prefetch-hit timing — the
    # "was the input pipeline ever the bottleneck" evidence. Resolved once
    # outside the loop; disabled runs take the bare q.get path.
    from tpuflow import obs

    obs_on = obs.enabled()
    try:
        while True:
            if obs_on:
                import time

                hit = not q.empty()
                t0 = time.monotonic()
                with obs.span("data.wait", hit=hit):
                    item = q.get()
                wait = time.monotonic() - t0
                obs.histogram("data.batch_wait_s", wait)
                # The overlap proof: ~0 on every hit means the input
                # pipeline ran entirely behind device compute.
                obs.gauge("data.host_wait_s", wait)
                if hit:
                    obs.counter("data.prefetch_hit")
                else:
                    obs.counter("data.prefetch_miss")
            else:
                item = q.get()
            if item is done:
                break
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        thread.join(timeout=1.0)


def get_dataloaders(
    batch_size: int,
    *,
    dataset: str = "fashion_mnist",
    val_only: bool = False,
    as_rows: bool = False,
    data_dir: str | None = None,
    seed: int = 0,
    shard_index: int = 0,
    num_shards: int = 1,
):
    """Parity entry point for the reference's ``get_dataloaders(batch_size,
    val_only, as_ray_ds)`` (my_ray_module.py:30-76): returns (train, val)
    ShardedLoaders, a val-only loader, or — with ``as_rows=True`` — the eval
    split as a list of ``{"features", "labels"}`` rows, matching the
    ``ray.data.from_items`` mode consumed by the batch-inference engine
    (my_ray_module.py:32-36,50)."""
    from tpuflow.data.datasets import load_dataset

    ds = load_dataset(dataset, data_dir=data_dir)
    if as_rows:
        return [
            {"features": ds.test.images[i], "labels": int(ds.test.labels[i])}
            for i in range(len(ds.test))
        ]
    val = ShardedLoader(
        ds.test,
        batch_size,
        shuffle=False,  # parity: val loader unshuffled (my_ray_module.py:74)
        pad_tail=True,
        drop_last=False,
    )
    # The registry's class count rides on the loaders so trainers can size
    # model heads from the data instead of re-deriving per dataset name.
    val.num_classes = ds.num_classes
    if val_only:
        return val
    train = ShardedLoader(
        ds.train,
        batch_size,
        shuffle=True,  # parity: train loader shuffled (my_ray_module.py:73)
        seed=seed,
        shard_index=shard_index,
        num_shards=num_shards,
    )
    train.num_classes = ds.num_classes
    return train, val
