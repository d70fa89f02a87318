"""On-demand ``jax.profiler`` capture with a bounded, serialized API.

The profiler is the tool of last resort an operator reaches for when
the metrics say "slow" but not "why" — and reaching for it must not
require redeploying with tracing compiled in. This module wraps
``jax.profiler.start_trace`` / ``stop_trace`` behind:

- ``capture(seconds, out_dir=None)`` — start a trace, sleep the
  bounded duration, stop, and return the artifact directory (open the
  contained ``*.trace.json.gz`` / xplane files in Perfetto or
  TensorBoard's profile plugin). Used programmatically by
  ``bench.py --profile`` and by tests.
- ``start_capture()`` / ``stop_capture()`` — the split pair for
  profiling a region whose duration the caller controls.
- ``GET/POST /debug/profile?seconds=N`` on
  ``exporters.MetricsHTTPServer`` — the zero-redeploy path: the
  endpoint runs one bounded ``capture`` and returns the artifact path.

Exactly ONE capture runs at a time (``ProfilerBusy`` otherwise — the
underlying profiler is a process-global singleton), durations are
clamped to ``MAX_SECONDS``, and a backend without profiler support
fails with ``ProfilerUnavailable`` and a clear message instead of a
deep jax traceback. Start/stop land in the flight recorder
(``profiler/capture_start`` / ``profiler/capture_done``) so captures
show up on the same timeline as the requests they overlapped.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Optional

#: Hard ceiling on one capture's duration: the endpoint must never be
#: talked into an unbounded trace that fills the disk.
MAX_SECONDS = 60.0


class ProfilerUnavailable(RuntimeError):
    """This backend cannot capture a profile."""


class ProfilerBusy(RuntimeError):
    """A capture is already in flight (the profiler is process-global)."""


_LOCK = threading.Lock()       # held for the whole capture
_STATE = threading.Lock()      # guards the _active_dir transition only
_active_dir: Optional[str] = None


def start_capture(out_dir: Optional[str] = None) -> str:
    """Begin one trace into ``out_dir`` (a fresh temp dir by default).
    Returns the artifact directory. Raises ``ProfilerBusy`` when a
    capture is already running, ``ProfilerUnavailable`` when the
    backend cannot trace."""
    global _active_dir
    if not _LOCK.acquire(blocking=False):
        raise ProfilerBusy(
            "a profiler capture is already in flight (the jax profiler "
            "is process-global); retry after it finishes")
    try:
        import jax.profiler as jp

        path = out_dir or tempfile.mkdtemp(prefix="bigdl_profile_")
        os.makedirs(path, exist_ok=True)
        try:
            # the Python tracer slows the host it is meant to observe,
            # and host level 2 costs the serving loop 100 ms a token gap
            # (PERF.md): level 1 keeps the program's TraceAnnotations,
            # what a traced benchmark run records
            opts = jp.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jp.start_trace(path, profiler_options=opts)
        except Exception as e:
            raise ProfilerUnavailable(
                f"profiler capture unsupported on this backend: "
                f"{e!r}") from e
        with _STATE:
            _active_dir = path
    except BaseException:
        _LOCK.release()
        raise
    from bigdl_tpu.observability.events import record
    record("profiler/capture_start", path=path)
    return path


def stop_capture(strict: bool = True) -> Optional[str]:
    """End the in-flight capture and return its artifact directory.
    With ``strict=False`` a missing capture returns None instead of
    raising — the idempotent form for timer/finally callers that race
    the natural end of a region."""
    global _active_dir
    with _STATE:
        if _active_dir is None:
            if strict:
                raise ProfilerBusy("no capture in flight")
            return None
        path, _active_dir = _active_dir, None
    try:
        import jax.profiler as jp
        jp.stop_trace()
    finally:
        # a plain Lock may be released by a thread other than the
        # acquirer — exactly what the timer/finally split needs
        _LOCK.release()
    from bigdl_tpu.observability.events import record
    record("profiler/capture_done", path=path)
    return path


def capturing() -> bool:
    return _active_dir is not None


def capture(seconds: float, out_dir: Optional[str] = None) -> str:
    """One bounded capture: start, sleep ``seconds`` (clamped to
    ``(0, MAX_SECONDS]``), stop. Returns the artifact directory."""
    import math

    seconds = float(seconds)
    if not math.isfinite(seconds) or seconds <= 0:
        raise ValueError(f"seconds must be a finite value > 0, "
                         f"got {seconds}")
    seconds = min(seconds, MAX_SECONDS)
    path = start_capture(out_dir)
    try:
        time.sleep(seconds)
    finally:
        stop_capture()
    return path
