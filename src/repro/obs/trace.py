"""Span-based host tracer with Chrome-trace-event (Perfetto) export.

One process-global tracer, off by default.  When enabled
(:func:`enable` / ``--trace out.json`` on the launch CLIs) every
:func:`span` brackets a host-side phase as a Chrome ``"X"`` (complete)
event — wall-clock ``ts``/``dur`` in microseconds plus a ``cpu_us``
process-time figure in ``args`` — and every :func:`instant` drops a
point event (plan provenance, overflow warnings, cache hits).  Spans on
the same thread nest naturally in the Perfetto timeline by interval
containment; the exported JSON (:meth:`Tracer.to_chrome` /
:func:`save`) loads directly in https://ui.perfetto.dev.

**Disabled fast path.**  The module-level :data:`on` flag is the
contract: hot call sites guard with ``if trace.on:`` (one module
attribute read, ~0.1us on this box — asserted by the overhead test in
``tests/test_obs.py``) and pay nothing else when tracing is off.
Cold call sites may call :func:`span` unguarded; it returns a shared
no-op context manager without allocating.

**Device work.**  The tracer never forces a device sync: a span around
a dispatched JAX computation measures *dispatch* time (JAX's async
dispatch returns before the device finishes).  Phases whose results are
synchronized anyway (host inspection ``int()`` syncs, the executor's
overflow-flag read, ``executor.wait``) are exact for free.  Device time
is attributed on the profiler's clock instead: :func:`enable` takes an
``annotate`` callable (``jax.profiler.TraceAnnotation``, passed by the
caller so this module stays dependency-free) and every span opens an
annotation of its own name for as long as it is open, so a profiler
session records the program's spans on the same clock as the device's
ops (``--profile DIR`` on the launch CLIs).  While enabled, Python's
garbage collections are spans too (``python.gc``, from
``gc.callbacks``).

This module is intentionally free of any repro.* (or third-party)
imports so every layer of the stack can use it without cycles.
"""
from __future__ import annotations

import gc
import json
import os
import threading
import time
from typing import Callable, Optional

# Module-level fast-path flag: hot call sites guard on `trace.on` and
# skip all span machinery when tracing is disabled.  enable()/disable()
# rebind it together with the tracer.
on: bool = False

_tracer: Optional["Tracer"] = None


def _jsonable(v):
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    try:                                   # numpy / jax scalars
        return v.item()
    except AttributeError:
        return str(v)


class _NullSpan:
    """Shared no-op span for the disabled path (never allocates)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


_NULL = _NullSpan()


class Span:
    """One live ``"X"`` event; use as a context manager (or begin/end)."""

    __slots__ = ("_tr", "name", "cat", "args", "_ts", "_cpu0", "_done",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self._tr = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._ts = 0
        self._cpu0 = 0
        self._done = False
        self._ann = None

    def set(self, **args) -> None:
        """Attach args discovered while the span is open (e.g. counts)."""
        self.args.update(args)

    def __enter__(self) -> "Span":
        if self._tr.annotate is not None:
            self._ann = self._tr.annotate(self.name)
            self._ann.__enter__()
        self._ts = time.perf_counter_ns()
        self._cpu0 = time.process_time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    def end(self) -> None:
        if self._done:
            return
        self._done = True
        dur_ns = time.perf_counter_ns() - self._ts
        cpu_ns = time.process_time_ns() - self._cpu0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        tr = self._tr
        args = {k: _jsonable(v) for k, v in self.args.items()}
        args["cpu_us"] = cpu_ns / 1e3
        tr.events.append({
            "name": self.name, "cat": self.cat, "ph": "X",
            "ts": (self._ts - tr.t0) / 1e3, "dur": dur_ns / 1e3,
            "pid": tr.pid, "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": args})


class Tracer:
    """Event sink for one tracing session (see :func:`enable`)."""

    def __init__(self, annotate: Optional[Callable] = None):
        self.events: list[dict] = []
        self.t0 = time.perf_counter_ns()
        self.pid = os.getpid()
        self.annotate = annotate
        self._gc: Optional[Span] = None

    def span(self, name: str, cat: str, args: dict) -> Span:
        return Span(self, name, cat, args)

    def instant(self, name: str, cat: str, args: dict) -> None:
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": (time.perf_counter_ns() - self.t0) / 1e3,
            "pid": self.pid,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": {k: _jsonable(v) for k, v in args.items()}})

    def to_chrome(self) -> dict:
        """The Chrome trace-event JSON object (loads in Perfetto)."""
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms",
                "otherData": {"tool": "repro.obs.trace",
                              "annotated": self.annotate is not None}}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path

    def on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook: one ``python.gc`` span per collection."""
        if phase == "start":
            self._gc = self.span("python.gc", "python",
                                 {"generation": info["generation"]})
            self._gc.__enter__()
        elif self._gc is not None:
            self._gc.set(collected=info["collected"])
            self._gc.end()
            self._gc = None


# ---------------------------------------------------------------------------
# Module API (the process-global tracer)


def enable(annotate: Optional[Callable] = None) -> Tracer:
    """Start a fresh tracing session; returns the live :class:`Tracer`.

    ``annotate(name)`` returns a context manager that each span enters
    when it opens and exits when it ends: with
    ``jax.profiler.TraceAnnotation`` the program's spans appear in a
    running profiler session, on the device trace's clock.
    """
    global _tracer, on
    disable()
    _tracer = Tracer(annotate=annotate)
    gc.callbacks.append(_tracer.on_gc)
    on = True
    return _tracer


def disable() -> None:
    global _tracer, on
    if _tracer is not None and _tracer.on_gc in gc.callbacks:
        gc.callbacks.remove(_tracer.on_gc)
    _tracer = None
    on = False


def active() -> bool:
    return _tracer is not None


def get() -> Optional[Tracer]:
    return _tracer


def span(name: str, cat: str = "mine", **args):
    """A span context manager; the shared no-op when tracing is off."""
    t = _tracer
    if t is None:
        return _NULL
    return t.span(name, cat, args)


def instant(name: str, cat: str = "event", **args) -> None:
    """A point event (plan provenance, warnings); no-op when off."""
    t = _tracer
    if t is not None:
        t.instant(name, cat, args)


def save(path: str) -> Optional[str]:
    """Write the current session's Chrome trace JSON; None when off."""
    t = _tracer
    if t is None:
        return None
    return t.save(path)
