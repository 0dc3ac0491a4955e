"""Span tracer: one ``span()`` feeding the JAX profiler and a Chrome JSON.

The trace is the "same clock" half of the observability story: autotune
trials, DP scheduling, serve request batches, and train steps all become
spans.  One ``span()`` call feeds two sinks:

* while :func:`repro.obs.enabled` (``obs.enable()``, or the launchers'
  ``--metrics-out``), each span also opens a
  ``jax.profiler.TraceAnnotation``, so inside a ``jax.profiler`` trace the
  program's spans land on the host plane of the same ``.xplane.pb`` as the
  device ops, on one clock.  The annotation carries the span's category
  as ``cat``, which tells the program's spans from the runtime's own host
  events; the other args are formatted into the event only for spans
  opened with ``profile_args=True``.  While enabled, every garbage
  collection is recorded as a ``host.gc`` span (``generation``,
  ``collected``), so that a pause can be put down to one;
* while a tracer is installed (:func:`start_trace`, or ``--trace
  FILE.json``), spans become *complete* events (``ph: "X"``) on one
  ``time.perf_counter`` timeline in Perfetto / chrome://tracing JSON.

Zero overhead when idle: with neither sink on, ``span()`` is one attribute
load, one branch and the shared no-op singleton — no allocation, no clock
read, no formatting; ``instant()`` likewise.  Install a tracer with
:func:`start_trace`, write it out with :func:`stop_trace` (or use the
:func:`tracing_to` context manager).

Output format of the JSON sink (the JSON Object Format of the Trace Event
spec, which Perfetto and chrome://tracing both accept):

    {"traceEvents": [{"name", "cat", "ph", "ts", "dur", "pid", "tid",
                      "args"}, ...],
     "displayTimeUnit": "ms",
     "otherData": {... provenance ...}}

``ts``/``dur`` are microseconds relative to the tracer's epoch.
"""
from __future__ import annotations

import gc
import json
import os
import threading
import time
from typing import Dict, List, Optional


class _NoopSpan:
    """Shared do-nothing span: the disabled-mode fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw):
        return self


NOOP_SPAN = _NoopSpan()


class Span:
    """One live span: a profiler annotation while :mod:`repro.obs` is
    enabled, and a complete ("X") event on the installed tracer, if any,
    when exited.  ``cpu=True`` adds the thread CPU time spent inside the
    span as the arg ``cpu_ms``."""

    __slots__ = ("_tracer", "_ann", "_profile_args", "_cpu", "name", "cat",
                 "args", "_t0", "_c0")

    def __init__(self, tracer: Optional["Tracer"], name: str, cat: str,
                 args: dict, profile_args: bool = False, cpu: bool = False):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._profile_args = profile_args
        self._cpu = cpu
        self._t0 = self._c0 = 0.0
        ann = _TRACE.annotation
        self._ann = (None if ann is None else
                     ann(name, cat=cat, **(args if profile_args else {})))

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        if self._cpu:
            self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def set(self, **kw):
        """Attach/overwrite args after the span opened (e.g. a measured
        verdict only known at exit)."""
        self.args.update(kw)
        if self._profile_args and self._ann is not None:
            self._ann.set_metadata(**kw)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._cpu:
            self.set(cpu_ms=(time.thread_time() - self._c0) * 1e3)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._tracer is not None:
            self._tracer._complete(self.name, self.cat, self._t0, t1,
                                   self.args)
        return False


class Tracer:
    """Collects trace events; thread-safe appends, one perf_counter epoch."""

    def __init__(self) -> None:
        self.events: List[dict] = []
        self._epoch = time.perf_counter()
        self._lock = threading.Lock()
        self._tids: Dict[int, int] = {}
        self._pid = os.getpid()

    def _tid(self) -> int:
        ident = threading.get_ident()
        t = self._tids.get(ident)
        if t is None:
            with self._lock:
                t = self._tids.setdefault(ident, len(self._tids))
        return t

    def _us(self, t: float) -> float:
        return (t - self._epoch) * 1e6

    def _complete(self, name: str, cat: str, t0: float, t1: float,
                  args: dict) -> None:
        ev = {"name": name, "cat": cat, "ph": "X",
              "ts": self._us(t0), "dur": max(self._us(t1) - self._us(t0), 0.0),
              "pid": self._pid, "tid": self._tid()}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def span(self, name: str, cat: str = "repro", **args) -> Span:
        return Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "repro", **args) -> None:
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "ts": self._us(time.perf_counter()),
              "pid": self._pid, "tid": self._tid()}
        if args:
            ev["args"] = args
        with self._lock:
            self.events.append(ev)

    def to_json(self, other_data: Optional[dict] = None) -> dict:
        meta = [{"name": "process_name", "ph": "M", "pid": self._pid,
                 "tid": 0, "args": {"name": "repro"}}]
        doc = {"traceEvents": meta + list(self.events),
               "displayTimeUnit": "ms"}
        if other_data:
            doc["otherData"] = other_data
        return doc

    def write(self, path: str, other_data: Optional[dict] = None) -> dict:
        doc = self.to_json(other_data)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return doc


# ---------------------------------------------------------------------------
# the sinks (module-level, like the registry's enabled flag)
# ---------------------------------------------------------------------------
class _TraceState:
    __slots__ = ("tracer", "annotation", "live", "gc_span")

    def __init__(self) -> None:
        self.tracer: Optional[Tracer] = None
        # jax.profiler.TraceAnnotation while repro.obs is enabled
        self.annotation = None
        # either sink on: span()'s one check
        self.live = False
        self.gc_span: Optional[Span] = None


_TRACE = _TraceState()


def _refresh() -> None:
    _TRACE.live = (_TRACE.tracer is not None
                   or _TRACE.annotation is not None)


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``host.gc`` span per collection."""
    if phase == "start":
        sp = span("host.gc", cat="host", profile_args=True,
                  generation=info["generation"])
        _TRACE.gc_span = sp.__enter__()
    elif _TRACE.gc_span is not None:
        sp, _TRACE.gc_span = _TRACE.gc_span, None
        sp.set(collected=info["collected"])
        sp.__exit__(None, None, None)


def annotate(on: bool) -> None:
    """Feed spans to the JAX profiler and record collections, or stop.
    :func:`repro.obs.enable` and :func:`repro.obs.disable` call this."""
    if on and _TRACE.annotation is None:
        from jax.profiler import TraceAnnotation
        _TRACE.annotation = TraceAnnotation
        gc.callbacks.append(_on_gc)
    elif not on and _TRACE.annotation is not None:
        _TRACE.annotation = None
        gc.callbacks.remove(_on_gc)
    _refresh()


def start_trace() -> Tracer:
    """Install (and return) a fresh global tracer."""
    _TRACE.tracer = Tracer()
    _refresh()
    return _TRACE.tracer


def stop_trace(path: Optional[str] = None,
               other_data: Optional[dict] = None) -> Optional[dict]:
    """Uninstall the tracer; write/return its JSON doc (None if not tracing)."""
    t, _TRACE.tracer = _TRACE.tracer, None
    _refresh()
    if t is None:
        return None
    if path is not None:
        return t.write(path, other_data)
    return t.to_json(other_data)


def tracing() -> bool:
    return _TRACE.tracer is not None


def current_tracer() -> Optional[Tracer]:
    return _TRACE.tracer


def span(name: str, cat: str = "repro", *, profile_args: bool = False,
         cpu: bool = False, **args):
    """A span on the live sinks, or the shared no-op when both are off.

    The no-op path is one attribute load and a branch — safe to leave in
    warm code.  Truly per-element hot loops (kernel grid steps, per-edge
    work) should not call even this.  ``profile_args=True`` formats
    ``args`` (and later ``set`` args) into the profiler event too, for the
    spans a trace reader needs them on; ``cpu=True`` records the thread CPU
    time spent inside as ``cpu_ms``.
    """
    if not _TRACE.live:
        return NOOP_SPAN
    return Span(_TRACE.tracer, name, cat, args, profile_args, cpu)


def instant(name: str, cat: str = "repro", **args) -> None:
    t = _TRACE.tracer
    if t is None:
        return
    t.instant(name, cat, **args)


class tracing_to:
    """``with obs.tracing_to("run.json"):`` — trace a block, write on exit."""

    def __init__(self, path: str, other_data: Optional[dict] = None):
        self.path = path
        self.other_data = other_data
        self.doc: Optional[dict] = None

    def __enter__(self) -> Tracer:
        return start_trace()

    def __exit__(self, *exc):
        self.doc = stop_trace(self.path, self.other_data)
        return False
