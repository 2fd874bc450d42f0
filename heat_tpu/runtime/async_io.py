"""Asynchronous checkpoint/telemetry pipeline: D2H + disk off the hot loop.

The reference stops the device for every host-visible event: its solution
dumps sit inline in the timed region (fortran/serial/heat.f90:77-83), and
our drive loop inherited that shape — ``sync(T_dev)`` -> full D2H fetch ->
synchronous ``checkpoint.save`` at every checkpoint boundary, seconds of
idle device per snapshot for GiB-scale fields.

This module is the off-critical-path half of the rework
(``backends.common.drive`` is the on-loop half): at a boundary the driver
takes ONE on-device buffer copy (donation-safe — the live field is donated
into the next chunk while the copy stays pinned for the writer) and resumes
stepping immediately; a background thread performs the device->host
transfer (``np.asarray`` on the snapshot blocks only the writer) and the
atomic-rename disk write.

Contract:

- **Bounded queue** (default depth 2): a slow sink applies BACKPRESSURE —
  ``submit`` blocks the driver when the queue is full — rather than
  accumulating unbounded device snapshots (each is a full field buffer;
  two in flight is the memory ceiling).
- **No snapshot is ever silently dropped**: ``drain`` flushes every queued
  snapshot before returning, and the driver calls it on BOTH the normal and
  the exception exit path (``drive``'s try/except).
- **Writer failures surface, promptly**: the first sink exception is
  re-raised on the next ``submit`` (the solve must not step for hours
  against a dead disk) and again at ``drain``; queued snapshots after a
  failed one are still attempted (independent files).
- **Transient sink errors are retried, bounded**: an ``OSError`` in the
  EIO/ENOSPC class (flaky NFS, momentary disk pressure) gets up to
  ``retries`` in-thread re-attempts under exponential backoff before it
  becomes a surfaced failure — a single I/O hiccup must not abort a
  day-long solve. Non-transient exceptions (fingerprint errors, NaN
  snapshot rejection) surface on the first attempt.
- **Drain is bounded**: ``drain(timeout_s=...)`` (default 10 min) raises
  ``TimeoutError`` instead of blocking the exit path forever on a hung
  sink; the daemon worker thread is abandoned (it cannot outlive the
  process).
- **Accounting**: ``busy_s`` (writer wall time in fetch+write), ``wait_s``
  (driver wall time blocked on the pipeline: backpressure + drain), and
  ``hidden_s = max(0, busy_s - wait_s)`` — the I/O wall time genuinely
  overlapped with compute, reported as ``Timing.overlap_s``.
"""

from __future__ import annotations

import errno
import queue
import threading
import time
from typing import Callable, Optional

from . import debug
from .logging import master_print

# Default queue depth: each entry pins one full-field device buffer, so the
# depth is a device-memory bound, not a tuning knob — 2 keeps one snapshot
# transferring while one more waits, which is all the pipelining a single
# writer thread can use.
DEFAULT_DEPTH = 2

# Transient-sink retry policy: 3 re-attempts at 50/100/200 ms covers the
# blip class (flaky NFS op, momentary ENOSPC from a log rotation) without
# stalling a genuinely dead disk for more than ~0.35 s before surfacing.
DEFAULT_RETRIES = 3
DEFAULT_RETRY_BACKOFF_S = 0.05

# drain() must never block an exit path forever (hung NFS mount): 10 min is
# far beyond any sane snapshot write yet still bounds the wait.
DEFAULT_DRAIN_TIMEOUT_S = 600.0

_TRANSIENT_ERRNOS = frozenset({
    errno.EIO, errno.ENOSPC, errno.EAGAIN, errno.EBUSY, errno.ETIMEDOUT,
    errno.EINTR,
})


class BoundedFetchTimeout(TimeoutError):
    """A watchdog-bounded device fetch did not complete in time (wedged
    device). The abandoned daemon thread may still be blocked on
    the transfer; the caller must treat the fetched-from state as lost."""


def bounded_call(fn: Callable[[], object], timeout_s: float,
                 what: str = "device fetch"):
    """Run ``fn`` in a daemon thread and wait at most ``timeout_s``.

    The boundary-fetch watchdog of the serving engine: a D2H transfer
    against a wedged device blocks uninterruptibly, so the only way to
    bound it is to move the blocking call off the waiting thread and
    abandon it on timeout (the same abandon-don't-wedge discipline as
    ``SnapshotWriter.drain``). Exceptions raised by ``fn`` re-raise here;
    a timeout raises ``BoundedFetchTimeout``."""
    result: list = [None, None]     # [value, exception]
    done = threading.Event()

    def runner():
        try:
            result[0] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            result[1] = e
        finally:
            done.set()

    t = threading.Thread(target=runner, daemon=True,
                         name="heat-bounded-fetch")
    t.start()
    if not done.wait(timeout_s):
        raise BoundedFetchTimeout(
            f"{what} did not complete within {timeout_s:g}s (wedged "
            f"device fetch?) — abandoning the fetch thread")
    if result[1] is not None:
        raise result[1]
    return result[0]


def is_transient(e: BaseException) -> bool:
    """The retry-worthy class: OS-level errors that routinely clear on
    their own. Anything else (fingerprint mismatch, NaN rejection, a
    coding bug) fails fast on the first attempt."""
    return isinstance(e, OSError) and e.errno in _TRANSIENT_ERRNOS


class SnapshotWriter:
    """Background writer for device snapshots with a bounded queue.

    ``submit(job)`` enqueues a zero-arg callable (closing over the device
    snapshot) and returns as soon as there is queue room; the worker thread
    runs jobs in FIFO order. Start is lazy (a solve with no checkpoint
    boundary never spawns a thread); the thread is a daemon so a crashed
    driver that never drains cannot hang interpreter exit.
    """

    def __init__(self, depth: int = DEFAULT_DEPTH,
                 retries: int = DEFAULT_RETRIES,
                 retry_backoff_s: float = DEFAULT_RETRY_BACKOFF_S,
                 tracer=None):
        # ``tracer`` (runtime/trace.py, optional): each job becomes one
        # span on the writer thread's track — the D2H + publish half of a
        # request/checkpoint made visible on the same timeline as the
        # compute it overlaps. Callers label jobs by setting a
        # ``job._trace = (name, trace_id)`` attribute; unlabeled jobs
        # trace as "io-job", and a job labeled None records its own span
        # (Tracer.span). No tracer (the default) costs nothing.
        self._tracer = tracer
        self._q: "queue.Queue[Optional[Callable[[], None]]]" = queue.Queue(
            maxsize=max(1, depth))
        self._thread: Optional[threading.Thread] = None
        # the one genuinely cross-thread cell: the worker publishes the
        # first sink error, submit/drain consume it. A ranked lock (not a
        # bare flag) so the hand-off is visible to the race sanitizer.
        self._exc_lock = debug.make_lock("writer:exc")
        self._exc: Optional[BaseException] = None
        self.retries = max(0, retries)
        self.retry_backoff_s = retry_backoff_s
        self.busy_s = 0.0     # writer wall time spent in D2H + disk write
        self.wait_s = 0.0     # driver wall time blocked on the pipeline
        self.submitted = 0
        self.completed = 0    # jobs RUN (successfully or not) — drained
        self.attempts = 0     # job executions incl. transient retries
        # race sanitizer (no-op unless HEAT_TPU_RACECHECK): the exempt
        # fields carry allow[races] markers above — instance-confined
        # driver-side accounting the static client+driver union merges
        debug.instrument_races(
            self, label="SnapshotWriter",
            exempt=frozenset({"wait_s", "submitted", "_thread"}))

    @property
    def hidden_s(self) -> float:
        """I/O wall time hidden behind compute (``Timing.overlap_s``)."""
        return max(0.0, self.busy_s - self.wait_s)

    def _run_job(self, job: Callable[[], None]) -> None:
        """One job with bounded transient retry. Retry sleeps count toward
        ``busy_s`` (the caller times around this call): a retrying writer IS
        occupying the pipeline, so the accounting stays honest about what
        compute could and couldn't hide."""
        for attempt in range(self.retries + 1):
            self.attempts += 1
            try:
                job()
                return
            except BaseException as e:  # noqa: BLE001 — surfaced at the
                # next submit/drain; later snapshots still attempted
                if not (is_transient(e) and attempt < self.retries):
                    with self._exc_lock:
                        if self._exc is None:
                            self._exc = e
                    return
                delay = self.retry_backoff_s * (2 ** attempt)
                master_print(f"async checkpoint writer: transient sink error "
                             f"({e}); retry {attempt + 1}/{self.retries} "
                             f"in {delay:.2g}s")
                time.sleep(delay)

    def _worker(self) -> None:
        while True:
            job = self._q.get()
            try:
                if job is None:  # drain sentinel
                    return
                t0 = time.perf_counter()
                try:
                    self._run_job(job)
                finally:
                    self.busy_s += time.perf_counter() - t0
                    self.completed += 1
                    tr = self._tracer
                    label = getattr(job, "_trace", ("io-job", None))
                    if tr is not None and tr.enabled and label is not None:
                        tr.complete(label[0], tr.thread_track("writer"), t0,
                                    cat="io", trace_id=label[1])
            finally:
                self._q.task_done()

    def _raise_pending(self) -> None:
        with self._exc_lock:
            exc, self._exc = self._exc, None
        if exc is not None:
            raise exc

    def submit(self, job: Callable[[], None]) -> None:
        """Enqueue a snapshot job; blocks when the queue is full
        (backpressure — bounded memory beats a snapshot pileup). Re-raises
        the first pending writer error instead of queueing behind it."""
        self._raise_pending()
        if self._thread is None:  # heat-tpu: allow[races] instance-confined — each writer's submit/drain side runs on the one thread that constructed it; the static client+driver union merges distinct instances
            self._thread = threading.Thread(
                target=self._worker, daemon=True, name="heat-snapshot-writer")
            self._thread.start()
        t0 = time.perf_counter()
        self._q.put(job)
        # heat-tpu: allow[races] instance-confined — same single-driver accounting as _thread above; the worker thread never touches these fields
        self.wait_s += time.perf_counter() - t0
        self.submitted += 1

    def drain(self, raise_errors: bool = True,
              timeout_s: Optional[float] = DEFAULT_DRAIN_TIMEOUT_S) -> None:
        """Flush every queued snapshot and stop the worker, within
        ``timeout_s`` (None = wait forever).

        ``raise_errors=False`` is the exception-exit form: snapshots still
        flush (nothing dropped) but a writer error is only logged — it must
        not mask the solve error already propagating. A drain that cannot
        finish inside the timeout (sink hung on a dead mount) raises
        ``TimeoutError`` (or logs, in the suppressed form) and abandons the
        daemon worker thread — bounded exit beats a wedged process."""
        t0 = time.perf_counter()
        hung = False
        if self._thread is not None:
            deadline = None if timeout_s is None else t0 + timeout_s
            try:
                # after all queued jobs: FIFO drain. The put itself can
                # block on a full queue behind a hung job — bound it too.
                self._q.put(None, timeout=None if deadline is None else
                            max(0.001, deadline - time.perf_counter()))
            except queue.Full:
                hung = True
            if not hung:
                self._thread.join(None if deadline is None else
                                  max(0.001, deadline - time.perf_counter()))
                hung = self._thread.is_alive()
            # heat-tpu: allow[races] instance-confined — drain runs on the writer's one driving thread; see submit
            self._thread = None  # abandoned if hung: daemon, dies with us
        self.wait_s += time.perf_counter() - t0
        if hung:
            msg = (f"async checkpoint writer failed to drain within "
                   f"{timeout_s:.0f}s (sink hung?) — abandoning the writer "
                   f"thread; queued snapshots may be lost")
            if raise_errors:
                raise TimeoutError(msg)
            master_print(msg)
            return
        if raise_errors:
            self._raise_pending()
        else:
            with self._exc_lock:
                exc = self._exc
            if exc is not None:
                master_print(f"async checkpoint writer error (suppressed "
                             f"while another error propagates): "
                             f"{type(exc).__name__}: {exc}")


def device_snapshot(T):
    """One on-device buffer copy of the live field.

    This is the whole on-loop cost of an async checkpoint: the copy is a
    device-side memcpy (HBM bandwidth, microseconds-to-milliseconds) that
    detaches the snapshot from the donation chain — the live buffer is
    donated into the next ``advance`` call while the copy stays pinned
    until the writer's ``np.asarray`` fetches and releases it. Works on
    sharded global arrays too (jitted identity, SPMD-uniform: every
    process copies its own shards)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if isinstance(T, jax.Array):
        return jnp.copy(T)
    return np.array(T)


def lane_snapshot(stacked, lane: int):
    """One-LANE on-device copy out of a stacked ``(L, ...)`` lane array
    (``device_snapshot``'s shape for the serving engine's dispatch-ahead
    extraction): the gather enqueues behind the chunks already in flight
    and produces its own buffer, detached from the donation chain, so the
    scheduler resumes dispatching immediately and only the writer thread
    ever blocks on the D2H. One lane, not the stack — a finished 256-side
    lane must not drag the other L-1 lanes' bytes across the link."""
    import jax
    import numpy as np

    if isinstance(stacked, jax.Array):
        return stacked[lane]
    return np.array(stacked[lane])
