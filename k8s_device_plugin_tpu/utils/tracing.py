"""Profiling/tracing hooks — an aux subsystem the reference lacks entirely
(SURVEY.md §5.1: no tracing, no pprof, vendored x/net/trace never imported).

Two layers:

- Workload (device) side: ``trace()`` wraps a region in a jax.profiler trace
  whose output loads in TensorBoard/XProf or Perfetto — XLA op timelines,
  HBM usage, ICI collective timing.  The serving loop's phases show up in
  it as ``engine.<phase>`` events (models/engine_profiler.py).
- Daemon (host) side: ``timed_rpc`` decorates gRPC servicer methods with
  wall-time logging, optional metrics-registry observation, AND a
  daemon-side span into the utils/spans.py ring — one tracing story with
  two entry points (request spans from the engine, RPC spans from the
  daemon); cheap enough to leave on (one monotonic pair per call).
"""

from __future__ import annotations

import contextlib
import functools
import logging
import os
import time
from typing import Iterator, Optional

from .spans import DAEMON_TRACE

log = logging.getLogger(__name__)

@contextlib.contextmanager
def trace(trace_dir: Optional[str], python_frames: bool = False) -> Iterator[None]:
    """Capture a jax.profiler trace of the enclosed region into
    ``trace_dir`` (no-op when trace_dir is falsy, so callers can wire it
    straight to an optional flag/env).  The one way this program starts
    a capture.

    Python's tracer is off unless ``python_frames``: under it
    ``sys.setprofile`` runs in every thread, which slows the host code
    being measured and fills the capture with a frame per call.  The
    host's TraceMe events stay either way: the runtime's own
    (``PjitFunction(...)``) and the serving loop's ``engine.<phase>``
    annotations (models/engine_profiler.py)."""
    if not trace_dir:
        yield
        return
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    log.info("profiler trace -> %s", trace_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = int(python_frames)
    with jax.profiler.trace(trace_dir, profiler_options=options):
        yield


def default_trace_dir(environ=None) -> Optional[str]:
    """Resolve the conventional trace-dir env (TPU_PLUGIN_TRACE_DIR)."""
    environ = os.environ if environ is None else environ
    return environ.get("TPU_PLUGIN_TRACE_DIR") or None


def timed_rpc(
    fn=None,
    *,
    observe=None,
    threshold_ms: float = 0.0,
    spans=None,
    name: Optional[str] = None,
):
    """Decorator for daemon RPC handlers: debug-log wall time per call,
    feed ``observe(seconds)`` (e.g. a metrics summary — the hook is
    unchanged), and record one daemon-side span per call into ``spans``
    — either a utils/spans.py SpanRecorder or a no-arg callable
    returning one/None (late binding: decoration happens before the
    daemon wires its recorder).  RPC spans carry the DAEMON_TRACE trace
    id, so the one span ring tells engine-request and kubelet-RPC
    timelines apart by trace.  ``threshold_ms`` promotes slow calls to
    WARNING."""

    def wrap(f):
        span_name = name or f"rpc.{f.__name__}"

        @functools.wraps(f)
        def inner(*args, **kwargs):
            t0 = time.monotonic()
            try:
                return f(*args, **kwargs)
            finally:
                end = time.monotonic()
                dt = end - t0
                if observe is not None:
                    observe(dt)
                recorder = spans() if callable(spans) else spans
                if recorder is not None:
                    recorder.record_span(
                        span_name,
                        DAEMON_TRACE,
                        start_monotonic=t0,
                        end_monotonic=end,
                    )
                if threshold_ms and dt * 1e3 >= threshold_ms:
                    log.warning("%s took %.1f ms", f.__name__, dt * 1e3)
                else:
                    log.debug("%s took %.2f ms", f.__name__, dt * 1e3)

        return inner

    return wrap if fn is None else wrap(fn)
