"""Process-local telemetry recorder: buffered structured JSONL events.

One ``Recorder`` per process writes ``events.p<proc>.jsonl`` under the
run's ``obs/`` directory (gang members inherit the directory through
``TPUFLOW_OBS_DIR`` and their slot through ``TPUFLOW_OBS_PROC`` /
``TPUFLOW_PROCESS_ID``); ``tpuflow.obs.timeline.merge_run_events`` unions
the per-process files into one run timeline.

Overhead contract (pinned by tests/test_obs.py):

- **Disabled** (no obs dir configured): every API call is one module-level
  boolean check — ``span()`` returns a shared no-op context manager,
  ``counter``/``gauge``/``histogram``/``event`` return immediately. No
  allocation, no locking, no I/O on the hot path.
- **Enabled**: ``record()`` appends a dict to an in-memory buffer under a
  lock — no file I/O on the caller's thread. A daemon thread flushes the
  buffer every ``flush_interval`` seconds (and on ``flush()``/``close()``/
  interpreter exit), so writes happen off the step critical path.

Event schema (one JSON object per line)::

    {"kind": "span|counter|gauge|histogram|event",
     "name": "<catalog name>", "ts": <wall-clock start, s>,
     "mono": <time.monotonic() at the same instant, s>,
     "proc": <gang process index>, "pid": <os pid>,
     "launch": <launch attempt, gang members only (TPUFLOW_ATTEMPT)>,
     "dur_s": <monotonic duration, spans only>,
     "span": <span id, unique in the process, spans only>,
     "parent": <id of the span open on the same thread when this one
                opened, or null; spans only>,
     "value": <counter/gauge/histogram payload>, ...attrs}

An enabled span also enters a ``jax.profiler.TraceAnnotation`` of its
name for its lifetime, in a process that has imported jax (the recorder
itself never imports it): under a profiler session the span lands on
the ``/host:`` plane of the same ``.xplane.pb`` as the device
operations, so an idle gap of the device can be put down to what the
program was doing. ``mono`` puts spans and point events (a request's
first token, its completion) on one clock; ``request=`` is the attribute
that ties a span to one serving request. A recorder's first event,
``obs.start``, marks where its clock begins; a closed span keeps its
``dur_s`` for a caller that files it elsewhere too.

The ``launch`` stamp (a dedicated key — several flow events already
carry their own ``attempt`` attribute, which must not be confused with
the process's launch number) is what lets the goodput ledger
(``tpuflow.obs.goodput``) stitch a requeued gang's attempts into one
per-run accounting with an explicit inter-attempt requeue-gap bucket.
The recorder also keeps a bounded ring of the most recent events — the
flight recorder's (``tpuflow.obs.flight``) forensic payload when the
process dies on a fatal path.
"""

from __future__ import annotations

import atexit
import collections
import itertools
import json
import os
import sys
import threading
import time
from typing import Any
from tpuflow.utils import knobs

_ENABLED = False
_RECORDER: "Recorder | None" = None
_ENV_CHECKED = False
_LOCK = threading.Lock()


class _NoopSpan:
    """Shared, reentrant no-op context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):  # matches _Span.set
        return self


_NOOP_SPAN = _NoopSpan()


# Span identity: ids are unique in the process (``next`` on a count is
# atomic under the GIL); the spans open on a thread form that thread's
# stack, so a span's parent is whatever its own thread had open.
_SPAN_IDS = itertools.count(1)
_OPEN = threading.local()
_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _open_spans() -> list[int]:
    try:
        return _OPEN.stack
    except AttributeError:
        _OPEN.stack = []
        return _OPEN.stack


def _annotation(name: str):
    """A profiler annotation for an enabled span, or None in a process
    that has not imported jax: the recorder never imports it itself."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _TRACE_ANNOTATION = getattr(profiler, "TraceAnnotation", None)
        if _TRACE_ANNOTATION is None:
            return None
    return _TRACE_ANNOTATION(name)


def ended_span(dur_s: float) -> dict:
    """The clock and identity fields of a span that someone else timed
    and that has just ended on this thread (JAX's compile listener):
    spread into ``record("span", name, **ended_span(d), ...)``."""
    stack = _open_spans()
    return {
        "ts": time.time() - dur_s,
        "mono": time.monotonic() - dur_s,
        "dur_s": dur_s,
        "span": next(_SPAN_IDS),
        "parent": stack[-1] if stack else None,
    }


class _Span:
    __slots__ = (
        "_rec", "_name", "_attrs", "_t0", "_ts", "_id", "_parent", "_ann",
        "dur_s",
    )

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self._rec = rec
        self._name = name
        self._attrs = attrs

    def set(self, **attrs):
        """Attach attributes discovered mid-span (e.g. bytes moved)."""
        self._attrs.update(attrs)
        return self

    def __enter__(self):
        stack = _open_spans()
        self._parent = stack[-1] if stack else None
        self._id = next(_SPAN_IDS)
        stack.append(self._id)
        self._ann = _annotation(self._name)
        if self._ann is not None:
            self._ann.__enter__()
        self._ts = time.time()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, *exc):
        self.dur_s = dur = time.monotonic() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, *exc)
        stack = _open_spans()
        if stack and stack[-1] == self._id:
            stack.pop()
        elif self._id in stack:  # closed out of order (a generator's span)
            stack.remove(self._id)
        if exc_type is not None:
            self._attrs.setdefault("error", exc_type.__name__)
        self._rec.record(
            "span", self._name, ts=self._ts, mono=self._t0, dur_s=dur,
            span=self._id, parent=self._parent, **self._attrs
        )
        return False


# Buffer bound: a wedged disk (every append raising OSError) must not let
# the in-memory buffer grow without limit for the rest of the run. Beyond
# the cap events are counted, not kept — and the count is surfaced as a
# final ``obs.dropped`` event at close, so loss is visible, never silent.
_DEFAULT_MAX_BUFFERED = 65536

# Flight-recorder ring: the last N events kept in memory regardless of
# flush/drop state, snapshotted into obs/flight/<proc>.json on fatal
# paths (tpuflow.obs.flight). Small on purpose — forensics, not history.
_DEFAULT_FLIGHT_RING = 256


class Recorder:
    """Buffered JSONL event writer for one process."""

    def __init__(
        self,
        directory: str,
        *,
        proc: int = 0,
        flush_interval: float = 0.5,
        max_buffered: int | None = None,
    ):
        self.directory = os.path.abspath(directory)
        self.proc = int(proc)
        # pid in the name: the HEAD runner and gang member 0 both occupy
        # logical slot 0 and may flush concurrently — distinct files make
        # every append single-writer (no torn lines to skip at merge).
        self.path = os.path.join(
            self.directory, f"events.p{self.proc:05d}.{os.getpid()}.jsonl"
        )
        os.makedirs(self.directory, exist_ok=True)
        self._buf: list[dict] = []
        if max_buffered is None:
            try:
                max_buffered = int(
                    knobs.raw("TPUFLOW_OBS_MAX_BUFFERED", "")
                    or _DEFAULT_MAX_BUFFERED
                )
            except ValueError:
                max_buffered = _DEFAULT_MAX_BUFFERED
        self._max_buffered = max(1, max_buffered)
        self.dropped = 0  # events lost to overflow or failed flushes
        # Launch attempt (gang members only): stamped into every event so
        # the goodput ledger can stitch requeued attempts into one run.
        self.attempt: int | None = None
        env_attempt = knobs.raw("TPUFLOW_ATTEMPT")
        if env_attempt:
            try:
                self.attempt = int(env_attempt)
            except ValueError:
                pass
        try:
            ring = int(
                knobs.raw("TPUFLOW_OBS_FLIGHT_RING", "")
                or _DEFAULT_FLIGHT_RING
            )
        except ValueError:
            ring = _DEFAULT_FLIGHT_RING
        self._ring: collections.deque = collections.deque(
            maxlen=max(0, ring)
        )
        self._lock = threading.Lock()
        self._closed = False
        self._flush_interval = flush_interval
        self._wake = threading.Event()
        self._thread = threading.Thread(
            target=self._flush_loop, daemon=True, name="tpuflow-obs-flush"
        )
        self._thread.start()
        # Where this recorder's clock starts: what a reader of set-up can
        # see begins here (the benchmark constructs it once the chip is
        # reached).
        self.record("event", "obs.start")

    # ------------------------------------------------------------- record
    def record(self, kind: str, name: str, *, ts: float | None = None,
               mono: float | None = None, **attrs) -> None:
        ev = {
            "kind": kind,
            "name": name,
            "ts": time.time() if ts is None else ts,
            "mono": time.monotonic() if mono is None else mono,
            "proc": self.proc,
            "pid": os.getpid(),
            **attrs,
        }
        if self.attempt is not None:
            ev.setdefault("launch", self.attempt)
        with self._lock:
            if self._closed:
                return
            # The flight ring sees every event — including ones the
            # bounded buffer is about to drop: the newest events are
            # exactly what a post-mortem needs.
            self._ring.append(ev)
            if len(self._buf) >= self._max_buffered:
                # Telemetry must never fail (or bloat) the run: beyond the
                # cap events are counted and dropped, surfaced at close.
                self.dropped += 1
                return
            self._buf.append(ev)

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def _ring_snapshot(
        self, timeout: float = 0.25
    ) -> tuple[list[dict], bool]:
        """Copy of the flight ring, signal-handler safe: tries the buffer
        lock with a timeout (the interrupted frame may hold it) and falls
        back to a best-effort lockless copy. Returns ``(events,
        lock_was_free)`` — when the lock could not be acquired, callers
        must not touch any locked recorder API (a signal handler doing so
        would deadlock against the frame it interrupted)."""
        got = self._lock.acquire(timeout=timeout)
        try:
            try:
                return list(self._ring), got
            except RuntimeError:  # mutated during the lockless iteration
                return [], got
        finally:
            if got:
                self._lock.release()

    # -------------------------------------------------------------- flush
    def _drain(self) -> None:
        with self._lock:
            buf, self._buf = self._buf, []
        if not buf:
            return
        lines = "".join(
            json.dumps(ev, default=_jsonable) + "\n" for ev in buf
        )
        try:
            with open(self.path, "a") as f:
                f.write(lines)
        except OSError:
            # Telemetry must never fail the run — but the drained batch
            # is gone; count it so close() can surface the loss.
            with self._lock:
                self.dropped += len(buf)

    def _flush_loop(self) -> None:
        while not self._closed:
            self._wake.wait(self._flush_interval)
            self._wake.clear()
            self._drain()

    def flush(self) -> None:
        self._drain()

    def close(self) -> None:
        if self._closed:  # idempotent (configure() then atexit)
            return
        self._closed = True
        self._wake.set()
        self._drain()
        if self.dropped:
            # Final accounting event, appended directly (the buffer is
            # closed): a consumer summing ``obs.dropped`` values knows
            # exactly how many events this process lost. Best-effort —
            # a still-broken disk loses the accounting line too.
            try:
                with open(self.path, "a") as f:
                    f.write(
                        json.dumps(
                            {
                                "kind": "event",
                                "name": "obs.dropped",
                                "ts": time.time(),
                                "proc": self.proc,
                                "pid": os.getpid(),
                                "value": self.dropped,
                            }
                        )
                        + "\n"
                    )
            except OSError:
                pass
        self._thread.join(timeout=2)


def _jsonable(v: Any):
    try:
        return float(v)
    except (TypeError, ValueError):
        return str(v)


# ----------------------------------------------------------- module API
def configure(
    directory: str | None, *, proc: int | None = None
) -> Recorder | None:
    """(Re)point the process recorder at ``directory``; ``None`` disables.

    The flow runner calls this at run start with ``<run_dir>/obs`` and at
    run end with ``None``; gang member processes pick the directory up
    from ``TPUFLOW_OBS_DIR`` automatically (see ``_maybe_init_from_env``).
    """
    global _ENABLED, _RECORDER, _ENV_CHECKED
    with _LOCK:
        _ENV_CHECKED = True  # explicit configure overrides env discovery
        if _RECORDER is not None:
            _RECORDER.close()
            _RECORDER = None
        _ENABLED = False
        if directory is None:
            return None
        if proc is None:
            proc = int(
                knobs.raw("TPUFLOW_OBS_PROC")
                or knobs.raw("TPUFLOW_PROCESS_ID")
                or 0
            )
        _RECORDER = Recorder(directory, proc=proc)
        _ENABLED = True
        return _RECORDER


def _maybe_init_from_env() -> None:
    """One-time env discovery: a gang subprocess (or any process launched
    with TPUFLOW_OBS_DIR set) starts recording without explicit wiring."""
    global _ENV_CHECKED
    if _ENV_CHECKED:
        return
    with _LOCK:
        if _ENV_CHECKED:
            return
        _ENV_CHECKED = True
    d = knobs.raw("TPUFLOW_OBS_DIR")
    if d:
        configure(d)


def enabled() -> bool:
    if not _ENV_CHECKED:
        _maybe_init_from_env()
    return _ENABLED


def recorder() -> Recorder | None:
    return _RECORDER if enabled() else None


def span(name: str, **attrs):
    """Timed region context manager; a shared no-op when disabled."""
    if not _ENABLED:
        if _ENV_CHECKED:
            return _NOOP_SPAN
        _maybe_init_from_env()
        if not _ENABLED:
            return _NOOP_SPAN
    return _RECORDER.span(name, **attrs)


def counter(name: str, value: float = 1, **attrs) -> None:
    if _ENABLED or enabled():
        _RECORDER.record("counter", name, value=value, **attrs)


def gauge(name: str, value: float, **attrs) -> None:
    if _ENABLED or enabled():
        _RECORDER.record("gauge", name, value=value, **attrs)


def histogram(name: str, value: float, **attrs) -> None:
    if _ENABLED or enabled():
        _RECORDER.record("histogram", name, value=value, **attrs)


def event(name: str, **attrs) -> None:
    if _ENABLED or enabled():
        _RECORDER.record("event", name, **attrs)


def flush() -> None:
    if _RECORDER is not None:
        _RECORDER.flush()


def timed_iter(iterable, name: str):
    """Yield from ``iterable``, recording the consumer-visible wait for
    each item as a ``histogram`` observation of ``name``. When telemetry
    is disabled this returns the original iterable untouched — zero
    wrapper frames on the hot path."""
    if not enabled():
        return iterable

    def _gen():
        it = iter(iterable)
        while True:
            t0 = time.monotonic()
            try:
                item = next(it)
            except StopIteration:
                return
            histogram(name, time.monotonic() - t0)
            yield item

    return _gen()


@atexit.register
def _atexit_close() -> None:
    if _RECORDER is not None:
        try:
            _RECORDER.close()
        except Exception:
            pass
