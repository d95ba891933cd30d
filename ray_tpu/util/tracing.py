"""Distributed tracing: span propagation across task submissions.

Reference: ``python/ray/util/tracing/tracing_helper.py:326,446`` — the
reference wraps every task/actor submission and execution in
OpenTelemetry spans, propagating the trace context inside the TaskSpec so
a nested task graph yields one cross-process trace. This redesign keeps
the propagation protocol (trace_id + parent_span_id ride the TaskSpec)
but exports spans through the existing GCS task-event sink instead of an
OTel collector: ``ray-tpu timeline`` merges them into the chrome trace
with flow arrows linking parent and child spans across processes.

Off by default (``RAY_TPU_TRACING=1`` enables): the hot path pays only
one env check when disabled.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

_local = threading.local()
_reporter = None
_reporter_lock = threading.Lock()


def enabled() -> bool:
    return os.environ.get("RAY_TPU_TRACING", "0") == "1"


def current() -> Optional[Tuple[str, str]]:
    """(trace_id, span_id) of the active span in this thread, if any."""
    return getattr(_local, "ctx", None)


def set_context(trace_id: str, span_id: str) -> None:
    _local.ctx = (trace_id, span_id)


def _live_core():
    """The current runtime, WITHOUT auto-initializing one (a flush thread
    must never resurrect a global worker after shutdown)."""
    from ray_tpu._private import worker as worker_mod

    w = getattr(worker_mod, "_global_worker", None)
    return None if w is None else w.core


def _get_reporter():
    global _reporter
    with _reporter_lock:
        if _reporter is None:
            from ray_tpu._private.events import BufferedPublisher

            def gcs_getter():
                core = _live_core()
                return getattr(core, "gcs", None) if core else None

            _reporter = BufferedPublisher("TASK_EVENT", gcs_getter)
        return _reporter


def _ids() -> str:
    return uuid.uuid4().hex[:16]


def _record(name: str, kind: str, trace_id: str, span_id: str,
            parent_span_id: Optional[str], ts: float, dur: float,
            **attrs) -> None:
    """Hand one SPAN task-event to the reporter: the one place that
    knows the record's shape (``ray-tpu timeline`` reads it back)."""
    _get_reporter().add({
        "state": "SPAN", "name": name, "kind": kind,
        "task_id": span_id,
        "trace_id": trace_id, "span_id": span_id,
        "parent_span_id": parent_span_id or "",
        "ts": ts, "dur": max(dur, 0.0), **_process_ids(), **attrs})


def gen_id() -> str:
    """A fresh 16-hex trace/span/request id (public: the serve plane
    mints request ids and pre-allocates span ids with it)."""
    return _ids()


def emit_span(name: str, *, trace_id: str, ts: float, dur: float,
              span_id: Optional[str] = None, parent_span_id: str = "",
              kind: str = "task", **attrs) -> str:
    """Record a span RETROSPECTIVELY with an explicit start/duration.

    The serve request path needs this because its phases are measured by
    bookkeeping (a request's queue wait ends when the admission loop
    picks it up, in a different thread than the one that submitted it),
    so a context manager around the work is impossible. Returns the span
    id ('' when tracing is disabled)."""
    if not enabled():
        return ""
    span_id = span_id or _ids()
    _record(name, kind, trace_id, span_id, parent_span_id, ts, dur, **attrs)
    return span_id


@contextmanager
def explicit_span(name: str, *, trace_id: str,
                  span_id: Optional[str] = None,
                  parent_span_id: str = "", kind: str = "task", **attrs):
    """Like :func:`span` but with a CALLER-CHOSEN span id, so the caller
    can hand that id to other processes as a parent BEFORE the span
    closes (the serve route span does this: engine lifecycle spans in
    the replica parent to it while the route call is still running).
    Sets the thread-local context so task submissions inside inherit
    the trace."""
    if not enabled():
        yield None
        return
    span_id = span_id or _ids()
    prev = current()
    set_context(trace_id, span_id)
    t0 = time.time()
    try:
        yield span_id
    finally:
        _local.ctx = prev
        _record(name, kind, trace_id, span_id, parent_span_id, t0,
                time.time() - t0, **attrs)


@contextmanager
def span(name: str, kind: str = "task",
         trace_id: Optional[str] = None,
         parent_span_id: Optional[str] = None, **attrs):
    """Run a span: sets the thread-local context (children submitted
    inside inherit it) and records a SPAN task-event on exit. With no
    explicit trace context, continues the current one or starts fresh."""
    if not enabled():
        yield None
        return
    with _span_impl(name, kind=kind, trace_id=trace_id,
                    parent_span_id=parent_span_id, **attrs) as s:
        yield s


@contextmanager
def _span_impl(name: str, kind: str = "task",
               trace_id: Optional[str] = None,
               parent_span_id: Optional[str] = None, **attrs):
    prev = current()
    if trace_id is None:
        if prev is not None:
            trace_id, parent_span_id = prev
        else:
            trace_id = _ids()
    span_id = _ids()
    set_context(trace_id, span_id)
    t0 = time.time()
    try:
        yield span_id
    finally:
        _local.ctx = prev
        _record(name, kind, trace_id, span_id, parent_span_id, t0,
                time.time() - t0, **attrs)


class phase:
    """One measurement of one stretch of a hot thread, read two ways::

        with tracing.phase("engine.upload", mdefs.CB_STEP_UPLOAD_MS, tags):
            ...

    The counter: the elapsed milliseconds are observed into ``hist``,
    always. The span: the same interval is a
    ``jax.profiler.TraceAnnotation``, which lands in the profiler's
    trace, on the device events' clock, while a profiler session is
    active (``ray-tpu profile``, ``benchmark/run.py --trace 1``) and
    costs a flag check otherwise; ``util/profile_gaps.py`` lays the
    device's idle gaps against these. A phase opened with
    ``outer=<the phase around it>`` takes its time out of that one's
    observation, so the two histograms add up to the outer interval.
    ``ms`` holds the whole elapsed time after the block; ``t0`` and ``t1``
    are the ``time.perf_counter()`` it began and ended at. ``RAY_TPU_TRACING``
    does not switch it: that gates the request spans, this is a metric.
    ``jax`` is imported on first use, so the control plane can import
    this module without it."""

    __slots__ = ("ms", "t1", "_hist", "_tags", "_outer", "_inner_ms",
                 "_annotation", "t0")
    _annotate = None    # jax.profiler.TraceAnnotation, once imported

    def __init__(self, name: str, hist, tags: Optional[Dict[str, str]] = None,
                 outer: Optional["phase"] = None):
        if phase._annotate is None:
            import jax.profiler

            phase._annotate = jax.profiler.TraceAnnotation
        self._annotation = phase._annotate(name)
        self._hist, self._tags, self._outer = hist, tags, outer
        self._inner_ms = 0.0
        self.ms = 0.0

    def __enter__(self) -> "phase":
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def elapsed_ms(self) -> float:
        """Milliseconds since the block was entered."""
        return (time.perf_counter() - self.t0) * 1e3

    def exclude(self, ms: float) -> None:
        """Take ``ms`` that passed inside the block out of its
        observation: time another measurement already books."""
        self._inner_ms += ms

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self.ms = (self.t1 - self.t0) * 1e3
        self._annotation.__exit__(*exc)
        self._hist.observe(self.ms - self._inner_ms, tags=self._tags)
        if self._outer is not None:
            self._outer._inner_ms += self.ms


def _process_ids() -> Dict[str, str]:
    core = _live_core()
    if core is None:
        return {"worker_id": "driver", "node_id": ""}
    return {"worker_id": getattr(core, "worker_id", "driver")[:12],
            "node_id": str(getattr(core, "node_id", ""))[:12]}


def inject_context(spec) -> None:
    """Stamp the active trace context into a TaskSpec before submission
    (reference: _inject_tracing_into_function). Creates a submit span so
    the executor-side span parents to this submission."""
    if not (enabled() or current() is not None):
        return
    ctx = current()
    if ctx is None:
        trace_id, parent = _ids(), ""
    else:
        trace_id, parent = ctx
    submit_span = _ids()
    _record(f"submit:{spec.name}", "submit", trace_id, submit_span, parent,
            time.time(), 0.0)
    spec.trace_id = trace_id
    spec.parent_span_id = submit_span


@contextmanager
def execute_span(spec, kind: str = "task"):
    """Executor-side span for a pushed task, parented to the submitter's
    span carried in the spec (the cross-process edge)."""
    if not getattr(spec, "trace_id", ""):
        yield None
        return
    with _span_impl(spec.name, kind=kind, trace_id=spec.trace_id,
                    parent_span_id=spec.parent_span_id) as s:
        yield s


def spans_to_chrome_events(records: List[Dict[str, Any]]) \
        -> List[Dict[str, Any]]:
    """SPAN task-events -> chrome trace X events + flow arrows linking
    parent to child (visible as arrows across process rows in
    chrome://tracing / perfetto)."""
    by_id = {r["span_id"]: r for r in records}
    out: List[Dict[str, Any]] = []
    for r in records:
        out.append({
            "name": r["name"], "cat": f"span:{r.get('kind', 'task')}",
            "ph": "X", "ts": r["ts"] * 1e6,
            "dur": max(r.get("dur", 0.0), 1e-5) * 1e6,
            "pid": r.get("node_id", ""), "tid": r.get("worker_id", ""),
            "args": {"trace_id": r["trace_id"], "span_id": r["span_id"],
                     "parent_span_id": r.get("parent_span_id", "")},
        })
        parent = by_id.get(r.get("parent_span_id", ""))
        if parent is not None:
            mid = parent["ts"] + max(parent.get("dur", 0.0), 0.0) / 2
            out.append({"name": "trace", "cat": "flow", "ph": "s",
                        "id": r["span_id"], "ts": mid * 1e6,
                        "pid": parent.get("node_id", ""),
                        "tid": parent.get("worker_id", "")})
            out.append({"name": "trace", "cat": "flow", "ph": "f",
                        "bp": "e", "id": r["span_id"],
                        "ts": r["ts"] * 1e6,
                        "pid": r.get("node_id", ""),
                        "tid": r.get("worker_id", "")})
    return out


__all__ = ["enabled", "span", "execute_span", "inject_context",
           "current", "set_context", "spans_to_chrome_events",
           "gen_id", "emit_span", "explicit_span", "phase"]
