"""TPU profiling: xprof traces + device-memory profiles via jax.profiler.

The reference's profiling story is (a) per-worker ProfileEvents to GCS
rendered by ``ray timeline`` (src/ray/core_worker/profiling.h:30; covered
here by utils/timeline.py) and (b) torch-profiler integration inside Train
(train/torch/train_loop_utils.py:232 TorchWorkerProfiler). On TPU the
equivalent of (b) is xprof: ``jax.profiler`` captures XLA device traces
(HLO timing, MXU utilization, HBM traffic) viewable in TensorBoard or
Perfetto. This module is the thin, dependency-gated bridge:

  - ``xprof_trace(logdir)``     capture a device trace for the enclosed code
                                (jax.profiler.trace), and record the span in
                                the runtime timeline so host-side task spans
                                and device traces line up;
  - ``annotate(name)``          a TraceAnnotation visible in xprof AND a
                                timeline span — one annotation, both views;
  - ``phase(acc, name)``        a TraceAnnotation ``rmt.engine.<name>`` plus
                                wall and thread-CPU seconds added to the
                                caller's accumulator; no timeline span (for
                                loops that run many times a second);
  - ``Flight``                  whether anything the loop dispatched is
                                still unread; ``phase(acc, name, flight)``
                                also adds the part of the block's wall
                                seconds in which nothing was (``starved_s``:
                                the chip had no work from this thread);
  - ``start_server(port)``      live-capture endpoint (connect TensorBoard's
                                profile tab to localhost:<port>);
  - ``save_device_memory_profile(path)``  HBM allocation snapshot (pprof
                                format) — the OOM-debugging tool.

All entry points degrade to no-ops with a warning when jax is unavailable
(CPU-only driver processes), so library code can call them unconditionally.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

from . import timeline


_resolved = None  # jax.profiler, or False without jax: looked up once


def _profiler():
    global _resolved
    if _resolved is None:
        try:
            import jax

            _resolved = jax.profiler
        except Exception:
            _resolved = False
    return _resolved or None


@contextlib.contextmanager
def xprof_trace(logdir: str, create_perfetto_trace: bool = False):
    """Capture an xprof/TensorBoard device trace of the enclosed block into
    ``logdir`` (the TorchWorkerProfiler analog for XLA)."""
    prof = _profiler()
    start = time.time()
    if prof is None:
        yield
        return
    try:
        with prof.trace(logdir,
                        create_perfetto_trace=create_perfetto_trace):
            yield
    finally:
        timeline.record_event("xprof_trace", "profiler", start, time.time(),
                              extra={"logdir": logdir})


@contextlib.contextmanager
def annotate(name: str):
    """Named region visible in BOTH the xprof device trace (TraceAnnotation)
    and the runtime chrome timeline."""
    prof = _profiler()
    start = time.time()
    ctx = prof.TraceAnnotation(name) if prof is not None \
        else contextlib.nullcontext()
    try:
        with ctx:
            yield
    finally:
        timeline.record_event(name, "annotation", start, time.time())


class Flight:
    """Whether anything a loop's thread handed the device is still unread,
    and the seconds in which nothing was: the queue **fills** when a
    program's call returns (``fill()``) and **drains** when the readback
    that takes the last outstanding result returns (``drain()``). One
    ``time.perf_counter`` read each: no array is touched, nothing is
    synchronised, ``is_ready()`` is not polled. Seconds with the queue empty
    are a **lower bound** of the device's idle time, on the host's clock: the
    way from a call's return to the program's start on the chip, and from
    the program's end to the readback's return, count as in flight. The
    loop's thread is the only caller."""

    __slots__ = ("_empty_at", "_closed")

    def __init__(self):
        self._empty_at: Optional[float] = time.perf_counter()
        self._closed = 0.0  # seconds of the empty stretches that have ended

    def fill(self) -> None:
        if self._empty_at is not None:
            self._closed += time.perf_counter() - self._empty_at
            self._empty_at = None

    def drain(self) -> None:
        self._empty_at = time.perf_counter()

    def empty_s(self, now: float) -> float:
        """Seconds the queue has been empty up to ``now`` (a
        ``perf_counter`` reading), since this object was made."""
        if self._empty_at is None:
            return self._closed
        return self._closed + (now - self._empty_at)


class phase:
    """One phase of a hot loop: ``with phase(acc, "emit"):`` adds the
    block's wall seconds (``time.perf_counter``) and the calling thread's
    CPU seconds (``time.thread_time``) to ``acc["emit"]``, a list
    ``[wall_s, cpu_s]`` the caller owns, and wraps the block in
    ``TraceAnnotation("rmt.engine.emit", **meta)``. Under a profiler
    session the annotation lands on the calling thread's line of the host
    plane, in the same ``.xplane.pb`` and on the same clock as the device
    planes; without a session it is a flag test. Wall minus CPU is what the
    thread spent off a core: waiting for the device, a lock or the GIL.

    With a :class:`Flight` (``phase(acc, "emit", flight)``) the list has a
    third item, ``starved_s``: the part of the block's wall seconds in which
    the flight's queue was empty, on the same two clock reads as the wall.
    A block between a drain and the next fill counts whole, the block in
    which the fill happens up to the call's return, the block in which the
    drain happens from the readback's return.

    Nothing goes to the timeline ring: a loop that runs tens of phases a
    second would only age out the task spans there."""

    __slots__ = ("_slot", "_ann", "_flight", "_t0", "_c0", "_e0")

    def __init__(self, acc, name: str, flight: Optional[Flight] = None,
                 **meta):
        self._slot = acc[name]
        self._flight = flight
        prof = _profiler()
        self._ann = prof.TraceAnnotation("rmt.engine." + name, **meta) \
            if prof is not None else None

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time()
        if self._flight is not None:
            self._e0 = self._flight.empty_s(self._t0)
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        # the block's end, read first: the CPU clock below is a system call,
        # one at which a loaded host's scheduler takes the core away, and
        # that is the timer's time, not the block's
        now = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        slot = self._slot
        slot[1] += time.thread_time() - self._c0
        wall = now - self._t0
        slot[0] += wall
        if self._flight is not None:
            # two differences of the same clock reads: held inside the
            # block's wall against their rounding, so that starved seconds
            # never pass wall seconds, block by block and so summed
            slot[2] += min(max(self._flight.empty_s(now) - self._e0, 0.0),
                           wall)
        return False


_server = None


def start_server(port: int = 9012) -> bool:
    """Start the live profiler server (TensorBoard profile tab target).
    Returns False when jax is unavailable."""
    global _server
    prof = _profiler()
    if prof is None:
        return False
    if _server is None:
        _server = prof.start_server(port)
    return True


def stop_server() -> None:
    global _server
    prof = _profiler()
    if prof is not None and _server is not None:
        prof.stop_server()
        _server = None


def save_device_memory_profile(path: str) -> Optional[str]:
    """Dump the current device (HBM) allocation profile in pprof format
    (``jax.profiler.save_device_memory_profile``); None if unavailable."""
    prof = _profiler()
    if prof is None:
        return None
    prof.save_device_memory_profile(path)
    return path
