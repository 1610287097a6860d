"""'raw' checkpoint format: manifest + per-shard binary files via native IO.

The fast path of the checkpoint subsystem (the 2 GB/s/chip north-star
metric): every pytree leaf is written as its device shards — one file per
distinct shard, written/read by the striped multi-threaded native ckptio
(tpuflow/_native/io.cpp) — plus a JSON manifest carrying paths / shapes /
dtypes / shard index offsets. No chunking, no compression, no gather:

- sharded leaves (FSDP states) never materialize the full array on save;
  each shard's device-local bytes go straight to its own file, so per-chip
  write bandwidth adds up exactly like the production multi-host model;
- replicated leaves (DP params) are written ONCE (replica 0), not per
  device — the dedup torch.save gets for free and Orbax also applies;
- restore is topology-free: shards are reassembled (or passed through when a
  single shard covers the array) and placed with any target sharding;
- partial restore (e.g. the params subtree for weights-only warm starts)
  reads only the matching files.

Multi-host: each process writes only the shards it owns (``replica_id == 0``
filter — disjoint across hosts, so per-host bandwidth adds up) plus a
manifest fragment; process 0 merges fragments into the unified manifest at
commit, after an all-hosts barrier. Restore reads only the files backing the
local devices of the target sharding. Orbax/ocdbt remains available via
``TPUFLOW_CKPT_FORMAT=orbax`` — both formats share the manager's layout and
policies.
"""

from __future__ import annotations

import errno
import json
import os
import random
import threading
import time
import weakref
import zlib
from typing import Any, Callable

import jax
import numpy as np

from tpuflow import _native
from tpuflow.utils import knobs

MANIFEST = "manifest.json"
FORMAT_NAME = "tpuflow-raw-v2"


class CorruptShardError(RuntimeError):
    """A shard file's bytes do not match the manifest (crc32 mismatch or
    truncation). Raised by restore-side verification so corrupted weights
    are never silently returned; the CheckpointManager catches it to fall
    back to the previous committed step."""


class CheckpointIOError(OSError):
    """A checkpoint storage operation failed for good: either a permanent
    error (EACCES, EROFS, ...) or a transient one that survived the whole
    retry budget (``retry_io``). The CheckpointManager treats a *save*
    dying this way as that step's save failing cleanly — partial staging
    reclaimed, ``ckpt.save_failed`` recorded, training continues — never
    as a member death; restores let it propagate (with tier/step fallback
    first)."""


# Errnos worth retrying: the storage layer hiccuped but the operation may
# well succeed on a fresh attempt (shared-filesystem brownouts, NFS/FUSE
# timeouts, device congestion). ENOSPC/EDQUOT are deliberately transient
# HERE: retention and the orphan GC free space between attempts, so "disk
# full" during a save is frequently a passing state, not a verdict.
_TRANSIENT_ERRNOS = frozenset(
    getattr(errno, name)
    for name in (
        "EIO", "EAGAIN", "EBUSY", "EINTR", "ETIMEDOUT", "ESTALE",
        "ENOSPC", "EDQUOT", "ENETDOWN", "ENETUNREACH", "ENETRESET",
        "ECONNRESET", "ECONNABORTED", "EREMOTEIO", "ENOLINK",
    )
    if hasattr(errno, name)
)

# Structural absence is a *semantic* outcome callers branch on (is this a
# committed step? does the subtree exist?), not a storage failure — those
# errors re-raise unchanged instead of being wrapped in CheckpointIOError.
_STRUCTURAL_ERRNOS = frozenset({errno.ENOENT, errno.ENOTDIR, errno.EISDIR})


def io_retries(default: int = 4) -> int:
    """Transient-failure retry budget per storage operation
    (``TPUFLOW_CKPT_IO_RETRIES``). 0 disables retrying; a malformed value
    falls back to ``default`` (checkpointing must never die on a typo'd
    env var mid-provisioning)."""
    env = knobs.raw("TPUFLOW_CKPT_IO_RETRIES")
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            pass
    return default


def io_backoff_s(default: float = 0.05) -> float:
    """Base backoff before the first retry (``TPUFLOW_CKPT_IO_BACKOFF_S``);
    doubles per attempt with 50-100% jitter so a gang's writers don't
    hammer a recovering filesystem in lockstep."""
    env = knobs.raw("TPUFLOW_CKPT_IO_BACKOFF_S")
    if env:
        try:
            return max(0.0, float(env))
        except ValueError:
            pass
    return default


def io_transient(e: OSError) -> bool:
    """Transient-vs-permanent classification of one storage error. Errors
    without an errno (wrapper layers, some FUSE stacks) count as transient
    — retrying a permanent error wastes a bounded few attempts, while NOT
    retrying a transient one fails a save that would have succeeded."""
    return e.errno is None or e.errno in _TRANSIENT_ERRNOS


def retry_io(
    fn: Callable[[], Any],
    *,
    op: str,
    path: str = "",
    _sleep: Callable[[float], None] = time.sleep,
):
    """Run one storage operation with transient-error retries.

    Every shard read/write, manifest dump, fsync-ing rename and upload
    copy in the checkpoint fast path goes through here: transient
    ``OSError``s (see ``io_transient``) are retried up to ``io_retries()``
    times with jittered exponential backoff from ``io_backoff_s()``,
    recording one ``ckpt.io_retry`` event per attempt; a permanent error
    or an exhausted budget records ``ckpt.io_error`` and raises
    :class:`CheckpointIOError` (structural absence — ENOENT and friends —
    re-raises unchanged; ``CorruptShardError`` passes straight through:
    integrity failures are never retried, re-reading corrupt bytes cannot
    help). ``fn`` must be safe to re-run from scratch — every call site
    rewrites its file from the start.
    """
    from tpuflow import obs

    retries = io_retries()
    backoff = io_backoff_s()
    attempt = 0
    while True:
        attempt += 1
        try:
            if knobs.raw("TPUFLOW_FAULT"):
                from tpuflow.testing import faults

                faults.ckpt_io_fault(op, path)
            return fn()
        except CorruptShardError:
            raise
        except OSError as e:
            if isinstance(e, CheckpointIOError):
                raise  # a nested retry_io already classified + recorded it
            name = os.path.basename(path.rstrip(os.sep)) if path else ""
            if e.errno in _STRUCTURAL_ERRNOS:
                raise
            if not io_transient(e):
                obs.event(
                    "ckpt.io_error", op=op, path=name, errno=e.errno,
                    attempts=attempt, transient=False, error=str(e)[:200],
                )
                raise CheckpointIOError(
                    f"{op} {path or '<unknown>'}: permanent storage error: {e}"
                ) from e
            if attempt > retries:
                obs.event(
                    "ckpt.io_error", op=op, path=name, errno=e.errno,
                    attempts=attempt, transient=True, error=str(e)[:200],
                )
                raise CheckpointIOError(
                    f"{op} {path or '<unknown>'}: transient storage error "
                    f"persisted through {attempt} attempts: {e}"
                ) from e
            delay = backoff * (2 ** (attempt - 1)) * (0.5 + 0.5 * random.random())
            obs.event(
                "ckpt.io_retry", op=op, path=name, attempt=attempt,
                delay_s=round(delay, 4), error=str(e)[:200],
            )
            _sleep(delay)


def _verify_enabled() -> bool:
    """Restore-side integrity verification (per-shard crc32 recorded in
    the manifest at save). On by default; ``TPUFLOW_CKPT_VERIFY=0`` opts
    out (e.g. to reclaim the checksum pass on trusted local storage or to
    keep zero-copy restores from touching every page)."""
    return knobs.raw("TPUFLOW_CKPT_VERIFY", "1") not in ("0", "false")


def _crc32(arr: np.ndarray) -> int:
    a = np.ascontiguousarray(arr)
    try:
        buf = memoryview(a).cast("B")
    except (TypeError, ValueError):
        buf = a.tobytes()  # extended dtypes without a buffer interface
    return zlib.crc32(buf)


def _check_shard_bytes(path: str, shard: dict, buf, nbytes: int) -> None:
    """Compare just-read shard bytes against the manifest record; shards
    saved before integrity stamping (no ``crc32`` key) pass vacuously."""
    want = shard.get("crc32")
    if want is None:
        return
    got = zlib.crc32(buf)
    if got != int(want):
        raise CorruptShardError(
            f"{path}: crc32 mismatch (manifest {int(want)}, file {got}, "
            f"{nbytes} bytes) — shard corrupted on storage"
        )

# (st_dev, st_ino) -> live-mapping refcount for shard files whose mapped
# pages escaped to a caller via zero_copy restore in this process: live
# restored arrays alias those pages, so the recycle pool must never
# overwrite the inodes in place (adopt_dir/take unlink them instead — the
# pages outlive the unlink). Inode identity is immune to cwd changes and
# symlinked path spellings; refcounts are released by a finalizer when the
# mapping is garbage-collected, so a reused inode number is not excluded
# forever. The cross-PROCESS hazard (another process recycling the same
# checkpoint directory while this one holds mappings) is documented on
# restore_raw.
_ALIASED_INODES: dict[tuple[int, int], int] = {}
_ALIASED_LOCK = threading.Lock()


def _register_alias_fd(fd: int) -> tuple[int, int]:
    st = os.fstat(fd)
    key = (st.st_dev, st.st_ino)
    with _ALIASED_LOCK:
        _ALIASED_INODES[key] = _ALIASED_INODES.get(key, 0) + 1
    return key


def _unregister_alias(key: tuple[int, int]) -> None:
    with _ALIASED_LOCK:
        n = _ALIASED_INODES.get(key, 0)
        if n <= 1:
            _ALIASED_INODES.pop(key, None)
        else:
            _ALIASED_INODES[key] = n - 1


def _is_aliased(path: str) -> bool:
    try:
        st = os.stat(path)
    except OSError:
        return False
    with _ALIASED_LOCK:
        return (st.st_dev, st.st_ino) in _ALIASED_INODES


def _mmap_enabled() -> bool:
    """Opt-in zero-copy restore via file mapping (TPUFLOW_CKPT_MMAP=1).

    OFF by default for a correctness reason: ``jax.device_put`` on CPU
    zero-copy *aliases* page-aligned host memory, so an array restored from a
    mapped shard file shares pages with that file — and the recycle pool
    overwrites retired shard files in place, which would silently mutate the
    restored array. Only enable for strictly read-only consumers of finished
    runs (e.g. batch eval); while enabled, this process's managers unlink
    retired files instead of recycling them (see RecyclePool.adopt_dir).
    """
    return knobs.raw("TPUFLOW_CKPT_MMAP", "0") == "1"


def _spare_cores() -> int:
    """Cores available for BACKGROUND page-backing beyond the one the
    host compute thread occupies. Background prewarm only wins when its
    page touches run on cores compute isn't using; on a 1-core box it
    steals the only core and measures actively harmful (an early CPU
    capture's prewarm_overlap: hidden_s -16.2 s, first save collapsed 8x). When
    this returns 0, background prewarms PARK their work: it runs only if
    a caller explicitly waits (prewarm_wait — that caller has nothing
    better to do with the core), else it never runs and the first save /
    restore pays exactly what it would have paid with no prewarm at all.
    Override: TPUFLOW_PREWARM_THREADS (0 parks, >=1 forces background).
    """
    env = knobs.raw("TPUFLOW_PREWARM_THREADS")
    if env is not None:
        try:
            return max(int(env), 0)
        except ValueError:
            pass
    return max((os.cpu_count() or 1) - 1, 0)


class RecyclePool:
    """Pool of retired shard files whose pages get reused by later saves.

    Retention hands doomed step directories to :meth:`adopt_dir`, which
    renames their ``.bin`` files into the pool instead of unlinking them;
    :meth:`take` hands a file back to a new save, which overwrites it in
    place (``write_bytes(..., inplace=True)``). On memory-backed storage
    (tmpfs staging tiers, page cache) this skips the fresh-page zeroing
    that otherwise dominates checkpoint write cost — steady-state per-epoch
    saves run at memcpy speed. Thread-safe: retention (main thread) and the
    async saver (background thread) share one pool.
    """

    def __init__(self, directory: str):
        self.directory = directory
        self._lock = threading.Lock()
        self._files: dict[int, list[str]] = {}  # size -> paths
        self._counter = 0
        self._warm_promised: dict[int, int] = {}
        self._warm_threads: list[threading.Thread] = []
        self._warm_cancel = threading.Event()
        self._deferred: list[int] = []  # sizes parked on a starved box
        if os.path.isdir(directory):
            for name in os.listdir(directory):
                path = os.path.join(directory, name)
                try:
                    self._files.setdefault(os.path.getsize(path), []).append(path)
                except OSError:
                    continue
                # Seed the name counter past every surviving pool file so a
                # restarted process never renames over a still-pooled inode.
                try:
                    self._counter = max(
                        self._counter, int(name[1:].split(".")[0])
                    )
                except (ValueError, IndexError):
                    self._counter += 1

    def adopt_dir(self, step_dir: str) -> None:
        """Absorb every ``.bin`` under ``step_dir`` and delete the rest."""
        import shutil

        if _mmap_enabled():
            # Restored arrays may alias these files' pages — never reuse
            # their inodes in place (see _mmap_enabled).
            shutil.rmtree(step_dir, ignore_errors=True)
            return
        # The step must become invisible before its payload is harvested: a
        # crash mid-adopt must not leave a committed-looking step with
        # missing shard files. (When adopting a bare state/ dir the caller
        # has already unlinked the metadata; this is then a no-op.)
        try:
            os.unlink(os.path.join(step_dir, "metadata.json"))
        except OSError:
            pass
        os.makedirs(self.directory, exist_ok=True)
        for root, _, names in os.walk(step_dir):
            for name in names:
                if not name.endswith(".bin"):
                    continue
                src = os.path.join(root, name)
                if _is_aliased(src):
                    # A live zero-copy restore maps this inode's pages:
                    # pooling it would let a later in-place overwrite mutate
                    # the restored arrays. rmtree below unlinks it instead
                    # (mapped pages outlive the unlink).
                    continue
                with self._lock:
                    self._counter += 1
                    dst = os.path.join(self.directory, f"r{self._counter:08d}.bin")
                    try:
                        size = os.path.getsize(src)
                        os.rename(src, dst)
                    except OSError:
                        continue
                    self._files.setdefault(size, []).append(dst)
        shutil.rmtree(step_dir, ignore_errors=True)

    def take(self, nbytes: int) -> str | None:
        """Pop a pooled file (exact-size match preferred) or None.

        Tiny requests (< 64 KiB, below the prewarm threshold) never draw
        from the pool: the in-place overwrite truncates the recycled file,
        so a small leaf would destroy a large warm file's pages for a
        fresh-write saving that is noise. The size-mismatch fallback
        likewise only hands out files at least as large as the request —
        their page prefix is reused and nothing warm is freed.
        """
        if nbytes < 64 * 1024:
            return None
        with self._lock:
            # Exact size first, then the smallest larger file (its page
            # prefix is reused; the truncated tail was surplus anyway).
            candidates = [nbytes] if nbytes in self._files else []
            candidates += sorted(
                s for s in self._files if s > nbytes
            )
            for size in candidates:
                bucket = self._files.get(size, [])
                while bucket:
                    path = bucket.pop()
                    if not bucket:
                        self._files.pop(size, None)
                    if _is_aliased(path):
                        # A live zero-copy mapping aliases this inode (it
                        # won the adopt/registration race): overwriting it
                        # in place would mutate restored arrays — unlink
                        # instead and keep looking.
                        try:
                            os.unlink(path)
                        except OSError:
                            pass
                        continue
                    return path
        return None

    def prewarm(self, sizes: list[int]) -> None:
        """Back pool pages for files of exactly ``sizes`` in the background.

        The first saves of a process's lifetime otherwise pay for growing
        the host's memory footprint (on ballooning hypervisors, first-touch
        of new guest pages runs ~15x slower than a steady-state write, and
        pages freed back to the host are reclaimed — so truncation waste
        re-pays the cost). Prewarming creates pool files of zeroed,
        *touched* pages at the exact shard sizes a save will request, while
        the caller does real work (epoch-1 compute in the trainer), so even
        the first checkpoint saves land on recycled pages at memcpy speed.
        Files enter the pool one by one — a save racing the prewarm simply
        consumes whatever is warm so far. Idempotent top-up: a repeated
        request only creates files not already pooled or being created by
        an in-flight prewarm (``_warm_promised`` tracks in-flight files
        only; fulfilled or failed promises are released, so a pool drained
        by saves can be topped up again). Sizes under 64 KiB are skipped
        (their fresh-write cost is noise).
        """
        sizes = sorted((s for s in sizes if s >= 64 * 1024), reverse=True)
        with self._lock:
            have: dict[int, int] = {
                s: len(v) for s, v in self._files.items()
            }
            for s, n in self._warm_promised.items():
                have[s] = have.get(s, 0) + n
            todo = []
            for s in sizes:
                if have.get(s, 0) > 0:
                    have[s] -= 1
                else:
                    todo.append(s)
                    self._warm_promised[s] = self._warm_promised.get(s, 0) + 1
            if not todo:
                return
            if _spare_cores() < 1:
                # Starved box: park the work instead of stealing the
                # compute core (see _spare_cores). Promises stay: a
                # repeated prewarm must not double-book the sizes.
                self._deferred.extend(todo)
                return
            t = threading.Thread(
                target=self._prewarm_run, args=(todo,), daemon=True
            )
            self._warm_threads.append(t)
        t.start()

    def _release_promise(self, size: int) -> None:
        n = self._warm_promised.get(size, 0)
        if n <= 1:
            self._warm_promised.pop(size, None)
        else:
            self._warm_promised[size] = n - 1

    def _prewarm_run(self, sizes: list[int]) -> None:
        os.makedirs(self.directory, exist_ok=True)
        # One small reused source buffer: its own pages get backed once,
        # while every written file page is a fresh first-touch (the cost
        # this thread exists to absorb off the save path).
        chunk = 32 * 2**20
        buf = b"\0" * chunk

        def abort(from_i: int, partial: str | None) -> None:
            # Drop the partial file and release every unfulfilled promise
            # so a later prewarm may retry (ENOSPC, cancel at close, ...).
            if partial is not None:
                try:
                    os.unlink(partial)
                except OSError:
                    pass
            with self._lock:
                for s in sizes[from_i:]:
                    self._release_promise(s)

        class _Cancelled(Exception):
            pass

        for i, size in enumerate(sizes):
            if self._warm_cancel.is_set():
                return abort(i, None)
            with self._lock:
                self._counter += 1
                path = os.path.join(self.directory, f"r{self._counter:08d}.bin")

            def write_warm_file() -> None:
                # Restart-from-scratch on retry ("wb" truncates): a partial
                # warm file must never enter the pool.
                with open(path, "wb", buffering=0) as f:
                    written = 0
                    while written < size:
                        if self._warm_cancel.is_set():
                            raise _Cancelled
                        f.write(buf[: min(chunk, size - written)])
                        written += min(chunk, size - written)

            try:
                # Through the retrying wrapper (ckpt.io_retry recorded):
                # a transient ENOSPC — retention/GC free space between
                # attempts — must not silently leave the warm file absent
                # and re-expose the first save to cold page-backing.
                retry_io(write_warm_file, op="prewarm", path=path)
            except _Cancelled:
                return abort(i, path)
            except (CheckpointIOError, OSError):
                return abort(i, path)
            with self._lock:
                self._files.setdefault(size, []).append(path)
                self._release_promise(size)

    def prewarm_wait(self, timeout: float | None = None) -> None:
        """Block until prewarmed files exist. ``timeout`` bounds the
        background-thread joins ONLY: on a starved box, parked work (see
        _spare_cores) executes in full on this caller's thread first,
        regardless of timeout."""
        with self._lock:
            threads = list(self._warm_threads)
            deferred, self._deferred = self._deferred, []
        if deferred:
            # The caller is blocking anyway — parked work (starved box,
            # see _spare_cores) runs here on the caller's own core.
            self._prewarm_run(sorted(deferred, reverse=True))
        for t in threads:
            t.join(timeout)

    def cancel_prewarm(self) -> None:
        """Stop in-flight prewarm promptly and join its threads (close());
        parked work is dropped, not executed."""
        self._warm_cancel.set()
        with self._lock:
            deferred, self._deferred = self._deferred, []
            for s in deferred:
                self._release_promise(s)
        self.prewarm_wait()
        self._warm_cancel.clear()

    def clear(self) -> None:
        import shutil

        self.cancel_prewarm()
        with self._lock:
            self._files.clear()
            self._warm_promised.clear()
            shutil.rmtree(self.directory, ignore_errors=True)


class RestoreArena:
    """Pre-backed destination buffers for restore reads.

    The restore-side mirror of the save-side ``RecyclePool``: on ballooning
    hypervisors the dominant cost of a cold restore is not moving the bytes
    but *backing the destination pages* (first-touch of fresh anonymous
    memory runs ~10x slower than memcpy on the dev host). The arena
    allocates and touches page-aligned buffers ahead of time — on a
    background thread that overlaps real startup work (data pipeline build,
    model compile) — and hands each out exactly once; ``jax.device_put`` on
    CPU then aliases the buffer zero-copy, so the restore critical path is a
    single page-cache memcpy into already-backed pages.

    Ownership is transfer-only: a taken buffer never returns to the arena
    (its pages belong to the restored array), so there is no reuse-while-
    aliased hazard. Sizes must match exactly — shard sizes are deterministic
    from the manifest, which is what ``prewarm`` is fed from. One restore
    per prewarm: ``restore_raw`` drops any unconsumed buffers when it
    finishes, so a prewarm whose restore took another shape (template
    mismatch, partial subtree, mmap) costs its backing work but never pins
    memory past the restore.
    """

    def __init__(self):
        self._buffers: dict[int, list[np.ndarray]] = {}
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        # Serializes background-prewarm spawns: without it two concurrent
        # prewarm() calls can race on self._thread and join a thread that
        # was created but not yet started.
        self._spawn_lock = threading.Lock()
        self._deferred: list[int] = []  # sizes parked on a starved box
        # Bumped by abandon(): an in-flight _back from an older generation
        # discards instead of landing — terminal reclamation without the
        # multi-GB join.
        self._gen = 0

    def prewarm(self, sizes: list[int], *, background: bool = True) -> None:
        """Allocate + page-back one buffer per entry of ``sizes``."""
        sizes = [int(s) for s in sizes if s > 0]
        if not sizes:
            return

        gen = self._gen

        def _run():
            self._back(sizes, gen)

        if background:
            if _spare_cores() < 1:
                # Starved box: park the work instead of stealing the
                # compute core (see _spare_cores); it runs only if a
                # caller explicitly blocks in prewarm_wait.
                with self._lock:
                    self._deferred.extend(sizes)
                return
            # One prewarm in flight at a time. The join of the previous
            # thread happens OUTSIDE the lock (it can last a multi-GB
            # page-touch), so prewarm_wait's brief locked read stays
            # bounded; the loop re-checks after joining because another
            # spawner may have won the slot meanwhile.
            while True:
                with self._spawn_lock:
                    prev = self._thread
                    if prev is None or not prev.is_alive():
                        t = threading.Thread(
                            target=_run,
                            name="tpuflow-restore-arena",
                            daemon=True,
                        )
                        t.start()  # started BEFORE publication: joiners
                        self._thread = t  # never see an unstarted thread
                        return
                prev.join()
        else:
            _run()

    def _back(self, sizes: list[int], gen: int | None = None) -> None:
        for s in sizes:
            with self._lock:
                if gen is not None and gen != self._gen:
                    return  # abandon()ed mid-flight: discard, don't land
            buf = _native.aligned_empty(s)
            buf[::4096] = 0  # touch every page: back it now, not at read
            if s % 4096:
                buf[-1] = 0
            with self._lock:
                if gen is not None and gen != self._gen:
                    return
                self._buffers.setdefault(s, []).append(buf)

    def prewarm_wait(self, timeout: float | None = None) -> None:
        """Block until prewarmed buffers have landed. ``timeout`` bounds
        the background-thread join ONLY: on a starved box, parked work
        (see _spare_cores) executes in full on this caller's thread
        first, regardless of timeout."""
        with self._lock:
            deferred, self._deferred = self._deferred, []
            gen = self._gen
        if deferred:
            # The caller is blocking anyway — parked work (starved box,
            # see _spare_cores) runs here on the caller's own core.
            self._back(deferred, gen)
        with self._spawn_lock:
            t = self._thread
        if t is not None:
            t.join(timeout)
            if not t.is_alive():
                with self._spawn_lock:
                    # Compare-and-swap: never clobber a spawn published
                    # after our read — losing the only reference to an
                    # in-flight prewarm would let clear() skip its join
                    # and leak the buffers it lands afterwards.
                    if self._thread is t:
                        self._thread = None

    def take(self, nbytes: int) -> np.ndarray | None:
        """Pop a pre-backed buffer of exactly ``nbytes``, else None."""
        with self._lock:
            stack = self._buffers.get(int(nbytes))
            return stack.pop() if stack else None

    def drop_present(self) -> None:
        """Drop buffers that have LANDED plus any parked (never-started)
        work, without joining an in-flight background prewarm — its
        still-unlanded buffers survive (they belong to the next
        restore). End-of-restore cleanup uses this."""
        with self._lock:
            self._buffers.clear()
            self._deferred.clear()

    def abandon(self) -> None:
        """Terminal reclamation without blocking: drop landed + parked
        buffers AND make any in-flight background prewarm discard its
        remaining work instead of landing it (generation bump — the
        thread keeps running but appends nothing). Used by
        CheckpointManager.close(): joining a possibly multi-GB page-touch
        there would block one manager's close on another's prewarm, while
        plain drop_present would let buffers landing moments later stay
        pinned for the process lifetime."""
        with self._lock:
            self._gen += 1
            self._buffers.clear()
            self._deferred.clear()

    def clear(self) -> None:
        with self._lock:
            self._deferred.clear()  # drop parked work, don't execute it
        self.prewarm_wait()
        with self._lock:
            self._buffers.clear()


_ARENA = RestoreArena()
# Process-wide restore serialization (see restore_raw): the arena hand-off
# and its end-of-restore cleanup are only safe one restore at a time.
_RESTORE_LOCK = threading.RLock()


def _path_names(path) -> list[str]:
    names = []
    for entry in path:
        if hasattr(entry, "key"):
            names.append(str(entry.key))
        elif hasattr(entry, "name"):
            names.append(str(entry.name))
        elif hasattr(entry, "idx"):
            names.append(str(entry.idx))
        else:
            names.append(str(entry))
    return names


def _leaf_shards(leaf) -> list[tuple[list[int], np.ndarray]]:
    """(start_indices, host_array) per locally-owned shard of a leaf.

    Ownership = ``replica_id == 0``: across the whole mesh exactly one copy
    of every distinct shard has replica 0, so N hosts each write only their
    own disjoint shard set (per-host write bandwidth adds up — the
    multi-host production model the ≥2 GB/s/chip target presumes) and
    replicated leaves are written exactly once globally. A host owning no
    replica-0 shard of a leaf returns [] for it.
    """
    if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
        out = []
        for shard in leaf.addressable_shards:
            if shard.replica_id != 0:
                continue
            starts = [
                (s.start or 0) for s in shard.index
            ]
            out.append((starts, np.asarray(shard.data)))
        return out
    # Non-jax leaves (host scalars, plain numpy) exist identically on every
    # process: the same ownership rule applies — process 0 writes, the rest
    # contribute no shard (otherwise N hosts race on one shared file).
    if jax.process_index() != 0:
        return []
    arr = np.asarray(leaf)
    return [([0] * arr.ndim, arr)]


def _dtype_str(d) -> str:
    """Manifest dtype spelling. Extended types (bfloat16, float8_*) have a
    raw-void ``.str`` ('<V2') that loses the type identity — their ``.name``
    parses back via the ml_dtypes registry; standard dtypes keep the
    endianness-explicit ``.str``."""
    d = np.dtype(d)
    return d.name if d.kind == "V" else d.str


def _gather_host(tree):
    """Device→host stage: (path, full_shape, dtype, shards).

    Every process lists every leaf (the pytree is global), each with only
    its locally-owned shards — possibly none on this process.

    All owned shards start their device→host copies ASYNC up front, then
    materialize in order: on real accelerators the DMA of shard N+1
    overlaps the numpy materialization of shard N instead of each
    ``np.asarray`` paying a serial round trip (a no-op on the CPU
    backend, where the buffers are already host-resident)."""
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    prefetch = True
    for _, leaf in leaves:
        if not prefetch:
            break
        if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
            for shard in leaf.addressable_shards:
                if shard.replica_id == 0:
                    try:
                        shard.data.copy_to_host_async()
                    except (AttributeError, RuntimeError):
                        # Platform without async D2H: abandon the whole
                        # prefetch (not just this leaf) — the sync path
                        # below handles everything.
                        prefetch = False
                        break
    out = []
    for path, leaf in leaves:
        shards = _leaf_shards(leaf)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            shape, dtype = list(leaf.shape), _dtype_str(leaf.dtype)
        else:
            # Pure-Python scalar/list leaves: derive shape/dtype the same way
            # _leaf_shards does, so processes that own no shard of the leaf
            # (every rank but 0) still emit a valid manifest entry.
            arr = np.asarray(leaf)
            shape, dtype = list(arr.shape), _dtype_str(arr.dtype)
        out.append((_path_names(path), shape, dtype, shards))
    return out


def _write_one(directory: str, fname: str, arr, pool: RecyclePool | None) -> None:
    dst = os.path.join(directory, fname)

    def attempt() -> None:
        recycled = pool.take(arr.nbytes) if pool is not None else None
        if recycled is not None:
            try:
                os.rename(recycled, dst)
                _native.write_bytes(dst, arr, inplace=True)
                return
            except OSError:
                pass  # fall through to a fresh write
        _native.write_bytes(dst, arr)

    retry_io(attempt, op="write_shard", path=dst)
    if knobs.raw("TPUFLOW_FAULT"):
        from tpuflow.testing import faults

        faults.corrupt_after_write(dst)


def _fs_is_memory_backed(path: str) -> bool:
    """True when ``path`` lives on tmpfs/ramfs (fsync is free there)."""
    try:
        best, fstype = "", ""
        path = os.path.abspath(path)
        with open("/proc/mounts") as f:
            for line in f:
                parts = line.split()
                if len(parts) < 3:
                    continue
                mnt = parts[1]
                # Path-boundary match: /run must not claim /runtime/ckpt.
                if (mnt == "/" or path == mnt or
                        path.startswith(mnt + "/")) and len(mnt) > len(best):
                    best, fstype = mnt, parts[2]
        return fstype in ("tmpfs", "ramfs")
    except OSError:
        return False


def _write_entries(
    directory: str, host_leaves, pool: RecyclePool | None = None
) -> None:
    """Write this process's shards. Single-process: the unified manifest is
    written directly. Multi-process: each process writes a manifest FRAGMENT
    (``manifest.p<rank>.json``) listing only the shards it owns; process 0
    merges fragments at commit time (``merge_manifests``) after the
    cross-process barrier, so the unified manifest — and hence step
    visibility — appears only once every host's shards are on storage.

    On memory-backed storage files are written sequentially (each write is
    already striped across threads, and fsync costs nothing). On real disks
    the per-file fsync waits on the device, so files are pipelined through a
    small thread pool: the memcpy of file N+1 overlaps the flush of file N
    (ctypes releases the GIL for the native write). Override the pool width
    with TPUFLOW_WRITE_CONCURRENCY; 1 forces sequential."""
    manifest = {
        "format": FORMAT_NAME,
        "process_count": jax.process_count(),
        "leaves": [],
    }
    jobs: list[tuple[str, Any]] = []
    for i, (names, shape, dtype, shards) in enumerate(host_leaves):
        entry = {"path": names, "shape": shape, "dtype": dtype, "shards": []}
        for starts, arr in shards:
            # Start coordinates are globally unique per distinct shard, so
            # hosts never collide on names and the merge is a plain union.
            coord = "x".join(map(str, starts)) or "0"
            fname = f"leaf_{i:05d}_{coord}.bin"
            jobs.append((fname, arr))
            entry["shards"].append(
                {
                    "file": fname,
                    "start": starts,
                    "shape": list(arr.shape),
                    # Content-integrity stamp, verified on restore
                    # (_check_shard_bytes). Computed here — on the async
                    # saver's thread — so the checksum pass never lands on
                    # the training critical path.
                    "crc32": _crc32(arr),
                }
            )
        manifest["leaves"].append(entry)
    width = int(knobs.raw("TPUFLOW_WRITE_CONCURRENCY", "0")) or (
        1 if _fs_is_memory_backed(directory) else 4
    )
    if width <= 1 or len(jobs) <= 1:
        for fname, arr in jobs:
            _write_one(directory, fname, arr, pool)
    else:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=min(width, len(jobs))) as ex:
            futures = [
                ex.submit(_write_one, directory, fname, arr, pool)
                for fname, arr in jobs
            ]
            for fut in futures:
                fut.result()  # propagate the first write error
    if jax.process_count() > 1:
        frag = os.path.join(directory, f"manifest.p{jax.process_index():05d}.json")

        def write_frag() -> None:
            with open(frag + ".tmp", "w") as f:
                json.dump(manifest, f)
            os.replace(frag + ".tmp", frag)

        retry_io(write_frag, op="write_manifest", path=frag)
        return
    unified = os.path.join(directory, MANIFEST)

    def write_unified() -> None:
        with open(unified, "w") as f:
            json.dump(manifest, f)

    retry_io(write_unified, op="write_manifest", path=unified)


def merge_manifests(directory: str, *, visibility_timeout_s: float = 10.0) -> None:
    """Union all manifest fragments into the unified manifest (process 0,
    after the all-hosts barrier). Fragments agree on leaf order/shape/dtype
    (the pytree is global); shard lists are disjoint unions.

    Merging FEWER fragments than the save's ``process_count`` would leave
    uncovered regions of restored arrays filled with uninitialized memory —
    but at the call site every writer has already reported success, so a
    shortfall is a transient visibility lag on eventually-consistent shared
    storage: poll briefly for the full set before failing loudly."""
    import time as _time

    deadline = _time.monotonic() + visibility_timeout_s
    while True:
        names = sorted(
            n for n in os.listdir(directory)
            if n.startswith("manifest.p") and n.endswith(".json")
        )
        expected = None
        if names:
            with open(os.path.join(directory, names[0])) as f:
                first = json.load(f)
            expected = int(first.get("process_count", len(names)))
            if len(names) >= expected:
                break
        if _time.monotonic() >= deadline:
            if not names:
                raise FileNotFoundError(f"no manifest fragments in {directory}")
            raise FileNotFoundError(
                f"{directory} has {len(names)} manifest fragments but the "
                f"save ran on {expected} processes; the step is incomplete "
                "on this storage (lagging sync or failed writer)"
            )
        _time.sleep(0.05)
    merged: dict | None = None
    for name in names:
        with open(os.path.join(directory, name)) as f:
            frag = json.load(f)
        if merged is None:
            merged = frag
            continue
        for entry, add in zip(merged["leaves"], frag["leaves"]):
            entry["shards"].extend(add["shards"])

    def write_merged() -> None:
        with open(os.path.join(directory, MANIFEST + ".tmp"), "w") as f:
            json.dump(merged, f)
        os.replace(
            os.path.join(directory, MANIFEST + ".tmp"),
            os.path.join(directory, MANIFEST),
        )

    retry_io(
        write_merged, op="write_manifest", path=os.path.join(directory, MANIFEST)
    )


def save_raw(directory: str, tree: Any, pool: RecyclePool | None = None) -> None:
    """Write ``tree`` synchronously."""
    os.makedirs(directory, exist_ok=True)
    _write_entries(directory, _gather_host(tree), pool)


class AsyncRawSaver:
    """Double-buffered async save: the device→host shard fetch happens
    synchronously (same contract as Orbax async — callers may donate device
    buffers immediately), file IO runs on a background thread.

    ``on_commit`` (if given) runs on the background thread strictly after all
    shard files are on disk — the manager uses it to write ``metadata.json``,
    so a step only becomes visible once its payload is complete (a crash
    mid-write leaves an invisible directory, reclaimed by the next manager's
    orphan sweep)."""

    def __init__(self):
        self._thread: threading.Thread | None = None
        self._error: list[BaseException] = []

    def save(
        self,
        directory: str,
        tree: Any,
        *,
        pool: RecyclePool | None = None,
        on_commit=None,
    ) -> None:
        self.wait()
        os.makedirs(directory, exist_ok=True)
        host_leaves = _gather_host(tree)

        def _write():
            try:
                _write_entries(directory, host_leaves, pool)
                if on_commit is not None:
                    on_commit()
            except BaseException as e:  # surfaced on next wait()
                self._error.append(e)

        self._thread = threading.Thread(target=_write, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error:
            raise self._error.pop()


def manifest_shard_sizes(
    directory: str, subtree: tuple[str, ...] | None = None
) -> list[int]:
    """Byte size of every shard file a restore of ``directory`` will read —
    the sizes ``RestoreArena.prewarm`` needs to pre-back the restore's
    destination buffers. One entry per unique shard file per leaf (the
    aligned restore path reads each file into exactly one buffer).
    ``subtree`` limits the sizes to a partial restore's leaves (e.g.
    ``('params',)`` for weights-only warm starts)."""
    manifest = _read_manifest(directory)
    sizes = []
    for entry in manifest["leaves"]:
        if subtree is not None and tuple(entry["path"][: len(subtree)]) != subtree:
            continue
        dtype = np.dtype(entry["dtype"])
        seen = set()
        for shard in entry["shards"]:
            if shard["file"] in seen:
                continue
            seen.add(shard["file"])
            n = int(np.prod(shard["shape"])) * dtype.itemsize
            sizes.append(n if shard["shape"] else dtype.itemsize)
    return sizes


def verify_dir(directory: str) -> tuple[int, list[str]]:
    """Recompute every shard file's crc32 against the manifest.

    Returns ``(shards_checked, bad_files)``. Shards without a recorded
    crc32 (checkpoints saved before integrity stamping) are skipped, and a
    non-raw directory checks nothing — both verify vacuously. Reads every
    byte once: an explicit audit, independent of the restore-time
    ``TPUFLOW_CKPT_VERIFY`` setting.
    """
    if not is_raw(directory):
        return 0, []
    manifest = _read_manifest(directory)
    checked = 0
    bad: list[str] = []
    seen: set[str] = set()
    for entry in manifest["leaves"]:
        dtype = np.dtype(entry["dtype"])
        for shard in entry["shards"]:
            fname = shard["file"]
            if fname in seen or shard.get("crc32") is None:
                continue
            seen.add(fname)
            checked += 1
            nbytes = (
                int(np.prod(shard["shape"])) * dtype.itemsize
                if shard["shape"]
                else dtype.itemsize
            )
            try:
                with open(os.path.join(directory, fname), "rb") as f:
                    data = f.read()
            except OSError:
                bad.append(fname)
                continue
            if len(data) < nbytes or zlib.crc32(data[:nbytes]) != int(
                shard["crc32"]
            ):
                bad.append(fname)
    return checked, bad


def is_raw(directory: str) -> bool:
    return os.path.exists(os.path.join(directory, MANIFEST))


def _read_manifest(directory: str) -> dict:
    path = os.path.join(directory, MANIFEST)

    def read() -> dict:
        with open(path) as f:
            return json.load(f)

    m = retry_io(read, op="read_manifest", path=path)
    if m.get("format") != FORMAT_NAME:
        raise ValueError(f"{directory}: not a {FORMAT_NAME} checkpoint")
    return m


def _read_shard(
    directory: str,
    shard: dict,
    dtype: np.dtype,
    *,
    allow_mmap: bool | None = None,
    threads: int | None = None,
    escapes: bool = True,
) -> np.ndarray:
    """Read (or map) one shard file.

    ``escapes=False`` promises the caller copies the returned array before
    it reaches user code (e.g. assembling a full leaf), so a mapping does
    not need the recycle-pool alias guard.
    """
    nbytes = int(np.prod(shard["shape"]) * dtype.itemsize) if shard["shape"] else dtype.itemsize
    path = os.path.join(directory, shard["file"])
    verify = _verify_enabled() and shard.get("crc32") is not None
    if verify:
        # Truncation pre-check: a torn/short file must fail loudly here,
        # not as an opaque native-reader error (or worse, garbage bytes).
        try:
            size = os.path.getsize(path)
        except OSError as e:
            raise CorruptShardError(f"{path}: unreadable shard ({e})") from e
        if size < nbytes:
            raise CorruptShardError(
                f"{path}: truncated shard ({size} bytes, manifest expects "
                f"{nbytes})"
            )
    if _mmap_enabled() if allow_mmap is None else allow_mmap:
        # Zero-copy: map the file's pages instead of reading into a fresh
        # buffer (copy-on-write so callers get a writable array without
        # touching the checkpoint). Consumers that place onto devices copy
        # exactly once, from the mapped pages — or alias them outright on
        # the CPU backend, hence the escape registration. The inode is
        # registered from OUR open fd before the mapping escapes, and the
        # path is re-checked afterwards: if the recycle pool adopted the
        # file in the registration window, the mapping is discarded and we
        # fall back to a plain copy (a freshly re-read one — the mapped
        # bytes could already be mid-overwrite).
        flat = None
        key = None
        try:
            f = open(path, "rb")
        except OSError:
            f = None
        if f is not None:
            try:
                if escapes:
                    key = _register_alias_fd(f.fileno())
                try:
                    flat = np.memmap(f, dtype=np.uint8, mode="c", shape=(nbytes,))
                except (OSError, ValueError):
                    flat = None  # zero-length/unmappable: fall through
            finally:
                f.close()
        if flat is not None and escapes:
            try:
                st = os.stat(path)
                same = (st.st_dev, st.st_ino) == key
            except OSError:
                same = False
            if not same:
                flat = None
        if flat is None:
            if key is not None:
                _unregister_alias(key)
        else:
            if key is not None:
                weakref.finalize(flat, _unregister_alias, key)
            if verify:
                # Forces the mapped pages in — the price of verifying a
                # zero-copy restore; TPUFLOW_CKPT_VERIFY=0 keeps it lazy.
                _check_shard_bytes(path, shard, flat, nbytes)
            return flat.view(dtype).reshape(shard["shape"])
    # Escaping reads draw their destination from the restore arena when a
    # pre-backed buffer of this exact size is available (transient reads —
    # escapes=False, copied into a full-leaf buffer — must not consume them).
    out = _ARENA.take(nbytes) if escapes else None
    buf = retry_io(
        lambda: _native.read_bytes(path, nbytes, threads=threads, out=out),
        op="read_shard",
        path=path,
    )
    if verify:
        _check_shard_bytes(path, shard, buf, nbytes)
    return buf.view(dtype).reshape(shard["shape"])


def _place(arr: np.ndarray, sharding) -> Any:
    """Host array → sharded jax.Array via per-shard placement.

    ``jax.device_put(arr, sharding)`` routes through a slow generic path for
    sharded layouts; assembling from per-device slices is the fast path (each
    device copies only its own contiguous window of the mapped pages).
    """
    shape = arr.shape
    try:
        index_map = sharding.addressable_devices_indices_map(shape)
        shards = []
        for device, index in index_map.items():
            piece = arr[index]
            if not (
                piece.flags["C_CONTIGUOUS"] and piece.ctypes.data % 64 == 0
            ):
                # Copy into an aligned buffer so device_put stays zero-copy.
                buf = _aligned_like(piece.shape, piece.dtype)
                buf[...] = piece
                piece = buf
            shards.append(jax.device_put(piece, device))
        return jax.make_array_from_single_device_arrays(shape, sharding, shards)
    except (TypeError, AttributeError, ValueError):
        return jax.device_put(arr, sharding)


def _resolve_index(index, shape) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """A device's index (tuple of slices) → (starts, extents)."""
    starts, extents = [], []
    for sl, dim in zip(index, shape):
        start = sl.start or 0
        stop = sl.stop if sl.stop is not None else dim
        starts.append(start)
        extents.append(stop - start)
    return tuple(starts), tuple(extents)


def _plan_entry(entry: dict, tmpl) -> list | None:
    """Aligned-restore plan for one manifest entry: ``[(device, shard), …]``
    when every device's required slice coincides with a saved shard file
    (restoring onto the sharding the state was saved with — the common
    case); None when host assembly + resharding is needed instead."""
    sharding = getattr(tmpl, "sharding", None)
    if sharding is None:
        return None
    shape = tuple(entry["shape"])
    try:
        index_map = sharding.addressable_devices_indices_map(shape)
        lookup = {
            (tuple(s["start"]), tuple(s["shape"])): s for s in entry["shards"]
        }
        placements = []
        for device, index in index_map.items():
            shard = lookup.get(_resolve_index(index, shape))
            if shard is None:
                return None
            placements.append((device, shard))
        return placements
    except (TypeError, AttributeError, ValueError):
        return None


def _cast(arr: np.ndarray, tmpl) -> np.ndarray:
    dtype = getattr(tmpl, "dtype", None)
    if dtype is None or arr.dtype == dtype:
        return arr
    # Casting into an aligned destination keeps the result eligible for the
    # zero-copy device_put path (see _native.aligned_empty).
    out = _aligned_like(arr.shape, np.dtype(dtype))
    out[...] = arr
    return out


def _aligned_like(shape, dtype: np.dtype) -> np.ndarray:
    # Scalars (shape ()) need one element; zero-size shapes need 0 bytes and
    # reshape fine from a 0-length view.
    nbytes = int(np.prod(shape)) * dtype.itemsize if shape else dtype.itemsize
    buf = _ARENA.take(nbytes)
    if buf is None:
        buf = _native.aligned_empty(nbytes)
    return buf.view(dtype).reshape(shape)


def _read_leaf(
    directory: str,
    entry: dict,
    *,
    threads: int | None = None,
    zero_copy: bool = False,
) -> np.ndarray:
    dtype = np.dtype(entry["dtype"])
    shards = entry["shards"]
    if len(shards) == 1 and shards[0]["shape"] == entry["shape"]:
        return _read_shard(
            directory,
            shards[0],
            dtype,
            threads=threads,
            allow_mmap=True if zero_copy else None,
        )
    full = _aligned_like(tuple(entry["shape"]), dtype)
    for shard in shards:
        idx = tuple(
            slice(start, start + dim)
            for start, dim in zip(shard["start"], shard["shape"])
        )
        # The copy into `full` makes the data private, so mapping the shard
        # file here is always safe (no alias escapes → no registration).
        full[idx] = _read_shard(
            directory, shard, dtype, allow_mmap=True, escapes=False
        )
    return full


def restore_raw(
    directory: str,
    abstract_state: Any | None = None,
    *,
    subtree: tuple[str, ...] | None = None,
    zero_copy: bool = False,
):
    """Restore a raw checkpoint.

    - With ``abstract_state`` (template pytree, same structure): leaves are
      matched in flatten order, cast to the template dtype and placed with
      the template's sharding when present.
    - Without a template: rebuilds a nested dict from manifest paths (works
      for dict-shaped trees like ``{"params": ...}``).
    - ``subtree``: restore only leaves whose path starts with this prefix,
      returned as the corresponding nested structure (partial restore).
    - ``zero_copy``: map shard files instead of reading them — restored
      arrays alias the files' page-cache pages (no buffer allocation, no
      copy; XLA's CPU client aliases page-aligned host memory), and data
      is paged in on first use. Sound in-process: every file whose mapping
      escapes is registered by inode, and RecyclePool.adopt_dir unlinks
      registered inodes instead of recycling them in place. NOT safe if a
      *different* process may recycle the same checkpoint directory while
      this one holds the arrays — use only for read-only consumers of runs
      this process owns or that are finished (batch eval, benches).
    """
    # Restores serialize on a process-wide lock: the arena is process-global
    # and its cleanup below would otherwise steal/drop the pre-backed
    # buffers of a concurrent restore (threads, or a prewarm for restore B
    # issued while restore A is in flight). Serialization preserves the
    # one-restore-per-prewarm contract; a prewarm issued mid-restore can
    # still lose (some of) its backing work to the cleanup — a lost
    # optimization, never a correctness problem.
    with _RESTORE_LOCK:
        try:
            return _restore_raw_inner(
                directory, abstract_state, subtree=subtree, zero_copy=zero_copy
            )
        finally:
            # Reclaim prewarmed-but-unconsumed arena buffers: a restore that
            # took a different path than its prewarm anticipated (template
            # mismatch → assemble fallback, partial-subtree read, mmap) must
            # not pin pre-backed pages for the process lifetime. One restore
            # per prewarm is the contract; leftovers die with the restore.
            # drop_present (not clear): an in-flight background prewarm for
            # the NEXT restore is not joined-and-discarded, so its
            # still-unlanded buffers survive for that restore.
            _ARENA.drop_present()


def _restore_raw_inner(
    directory: str,
    abstract_state: Any | None = None,
    *,
    subtree: tuple[str, ...] | None = None,
    zero_copy: bool = False,
):
    manifest = _read_manifest(directory)
    entries = manifest["leaves"]
    if subtree is not None:
        entries = [
            e for e in entries if tuple(e["path"][: len(subtree)]) == subtree
        ]
        if not entries:
            raise KeyError(f"no leaves under {subtree} in {directory}")

    if abstract_state is not None and subtree is None:
        flat, treedef = jax.tree_util.tree_flatten(abstract_state)
        if len(flat) != len(entries):
            raise ValueError(
                f"template has {len(flat)} leaves, checkpoint {len(entries)}"
            )
        # Restore parallelism is at SHARD granularity: every (device, shard
        # file) pair is an independent read+place task (file IO and device
        # copies are C++-side with the GIL released), so faults and copies
        # overlap across all cores — the multi-host analogue is every host
        # reading only its own shards concurrently.
        from concurrent.futures import ThreadPoolExecutor

        aligned = [_plan_entry(entry, tmpl) for tmpl, entry in zip(flat, entries)]

        # One task per unique shard FILE: replicated leaves map several
        # devices onto one file, which is read once and placed per device
        # inside the task (no IO amplification). Sharded leaves get one task
        # per shard. File IO and device copies are C++-side with the GIL
        # released, so tasks overlap across cores.
        grouped = []  # per aligned entry: list[(shard, [devices])]
        n_tasks = 0
        for plan in aligned:
            if plan is None:
                n_tasks += 1
                grouped.append(None)
                continue
            by_file: dict[str, tuple[dict, list]] = {}
            for dev, shard in plan:
                by_file.setdefault(shard["file"], (shard, []))[1].append(dev)
            grouped.append(list(by_file.values()))
            n_tasks += len(by_file)
        # IO-bound concurrency floor: restore tasks spend their time
        # blocked on the device (cold reads) or page faults, not running
        # on a core, so capping workers at cpu_count starves the device's
        # queue depth on low-core hosts — measured on the 1-core dev box:
        # cold disk restore 1.10 GB/s with 1 worker vs a 1.81 GB/s
        # 2-stream device ceiling. The
        # floor of 4 matches the write path's pipeline width. An EXPLICIT
        # TPUFLOW_IO_THREADS is a user cap on inflight IO (e.g. to stay
        # polite on shared storage) — it wins over the floor.
        budget = _native.default_threads()
        if not knobs.is_set("TPUFLOW_IO_THREADS"):
            budget = max(budget, 4)
        workers = min(n_tasks, budget) or 1
        # Each pooled task gets its slice of the FLOORED budget (not the
        # raw core count): a checkpoint with fewer shard files than the
        # floor still drives the device at full width by striping each
        # file over more native-reader threads — total inflight stays
        # ~budget regardless of how the tree groups into files.
        read_threads = max(1, budget // workers)

        def read_group(entry, tmpl, shard, devices):
            arr = _cast(
                _read_shard(
                    directory,
                    shard,
                    np.dtype(entry["dtype"]),
                    threads=read_threads,
                    allow_mmap=True if zero_copy else None,
                ),
                tmpl,
            )
            return [jax.device_put(arr, dev) for dev in devices]

        def assemble_fallback(entry, tmpl):
            arr = _cast(
                _read_leaf(
                    directory, entry, threads=read_threads, zero_copy=zero_copy
                ),
                tmpl,
            )
            sharding = getattr(tmpl, "sharding", None)
            return _place(arr, sharding) if sharding is not None else arr

        with ThreadPoolExecutor(workers) as pool:
            futures = []
            for (tmpl, entry), groups in zip(zip(flat, entries), grouped):
                if groups is None:
                    futures.append(
                        (None, pool.submit(assemble_fallback, entry, tmpl))
                    )
                else:
                    futures.append(
                        (
                            (tmpl, entry),
                            [
                                pool.submit(read_group, entry, tmpl, shard, devs)
                                for shard, devs in groups
                            ],
                        )
                    )
            out = []
            for key, fs in futures:
                if key is None:
                    out.append(fs.result())
                else:
                    tmpl, entry = key
                    shards = [a for f in fs for a in f.result()]
                    out.append(
                        jax.make_array_from_single_device_arrays(
                            tuple(entry["shape"]), tmpl.sharding, shards
                        )
                    )
        return jax.tree_util.tree_unflatten(treedef, out)

    # Path-based nested-dict reconstruction.
    root: dict = {}
    for entry in entries:
        names = entry["path"][len(subtree) :] if subtree else entry["path"]
        arr = _read_leaf(directory, entry, zero_copy=zero_copy)
        if not names:
            return arr  # the subtree was a single leaf
        node = root
        for name in names[:-1]:
            node = node.setdefault(name, {})
        node[names[-1]] = arr
    return root
