"""Spans on the profiler's clock, with Chrome-trace/Perfetto export.

Every span the program records goes through ``span``. It always opens a
``jax.profiler.TraceAnnotation`` (a TraceMe): with no profiler capture
running that costs one check, and under a capture the span lands on the
host plane of the ``.xplane.pb`` beside the device's operations, on the
same timeline, with its arguments as metadata. Argument strings are built
only while a capture records. The profiler cuts a value at ``,``, ``#``
or ``=``, so those become spaces, and lists (request uids) are joined
with spaces: ``uids="3 4 5"``.

A request's life is submit → queue → coalesce → dispatch → device
solve → fold → respond, and under the fleet those stages happen in
*different processes*. With a ``Tracer`` attached, the same ``span``
also records a complete Chrome event ("X" phase) stamped with a shared
``trace`` id; the dispatcher puts the id on the solve frame, the worker
tags its spans with the same id and ships them back on the result
frame, and ``export`` writes one JSON all the spans stitch together in.

Chrome timestamps are epoch microseconds, so spans from different
processes land on one timeline. They are the span's own ``perf_counter``
readings plus one offset to ``time.time`` fixed when the ``Tracer`` is
created. The event buffer is a bounded deque — a long-lived server
keeps the most recent window, never grows without limit.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Iterable

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "Span", "span", "host_sync"]

# the profiler's metadata ends a value at these characters
_CUT = str.maketrans({",": " ", "#": " ", "=": " "})


def _arg(v):
    """One span argument as the profiler's metadata holds it."""
    if isinstance(v, (int, float)):
        return v
    if isinstance(v, (list, tuple)):
        v = " ".join(str(x) for x in v)
    return str(v).translate(_CUT)


def _annotation(name: str, args: dict) -> TraceAnnotation:
    if not args or not TraceAnnotation.is_enabled():
        return TraceAnnotation(name)
    meta = {k: _arg(v) for k, v in args.items()}
    if "step_num" in args:
        meta["_r"] = 1          # a step span, as StepTraceAnnotation marks it
    return TraceAnnotation(name, **meta)


class Span:
    """One span: a TraceMe on the calling thread and, with a ``Tracer``,
    a Chrome event from the same ``perf_counter`` readings."""

    __slots__ = ("name", "cat", "tracer", "trace", "args", "_me", "_t0")

    def __init__(self, name: str, tracer: "Tracer | None",
                 trace: str | None, args: dict, cat: str | None = None):
        self.name = name
        self.cat = cat if cat is not None else name.split(".", 1)[0]
        self.tracer = tracer
        self.trace = trace
        self.args = args

    def __enter__(self) -> "Span":
        self._me = _annotation(self.name, self.args)
        self._me.__enter__()
        if self.tracer is not None:
            self._t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        """Arguments known only inside the span, such as a decision."""
        self.args.update(args)
        if TraceAnnotation.is_enabled():
            self._me.set_metadata(**{k: _arg(v) for k, v in args.items()})

    def __exit__(self, *exc) -> bool:
        if self.tracer is not None:
            self.tracer.record(self.name, self._t0, time.perf_counter(),
                               cat=self.cat, trace=self.trace,
                               args=self.args)
        self._me.__exit__(*exc)
        return False


def span(name: str, *, tracer: "Tracer | None" = None,
         trace: str | None = None, **args) -> Span:
    """Context manager: one span named ``name`` on the profiler's clock.

    ``args`` become the span's metadata (``uids=[3, 4]`` reads back as
    ``"3 4"``); ``step_num=`` makes it a step span. With ``tracer`` the
    span is also a Chrome event, tagged with ``trace``, of category
    ``name``'s first dotted part."""
    return Span(name, tracer, trace, args)


def host_sync(site: str, *, registry=None, tracer=None) -> Span:
    """Span ``host.sync`` around one blocking device-to-host read, and one
    more on the registry's ``serve.host_syncs`` counter."""
    if registry is not None:
        registry.counter("serve.host_syncs").inc()
    return Span("host.sync", tracer, None, {"site": site})


class Tracer:
    """Bounded in-process span recorder, wire-shippable and exportable."""

    def __init__(self, max_events: int = 65536, pid: int | None = None) -> None:
        self._lock = threading.Lock()
        self._events: deque[dict] = deque(maxlen=max_events)
        self._pending: deque[dict] = deque(maxlen=max_events)
        self.pid = os.getpid() if pid is None else pid
        # perf_counter seconds -> epoch seconds, fixed once
        self._epoch_s = time.time() - time.perf_counter()

    def add(
        self,
        name: str,
        *,
        cat: str = "serve",
        ts_us: float,
        dur_us: float,
        trace: str | None = None,
        args: dict | None = None,
        pid: int | None = None,
        tid: int | None = None,
    ) -> None:
        """Record one complete span (used for spans timed externally)."""
        ev = {
            "name": name,
            "cat": cat,
            "ph": "X",
            "ts": ts_us,
            "dur": dur_us,
            "pid": self.pid if pid is None else pid,
            "tid": threading.get_ident() % 2**31 if tid is None else tid,
        }
        a = dict(args) if args else {}
        if trace is not None:
            a["trace"] = trace
        if a:
            ev["args"] = a
        with self._lock:
            self._events.append(ev)
            self._pending.append(ev)

    def record(self, name: str, t0: float, t1: float, *, cat: str,
               trace: str | None = None, args: dict | None = None) -> None:
        """Record a span between two ``perf_counter`` readings."""
        self.add(name, cat=cat, ts_us=(t0 + self._epoch_s) * 1e6,
                 dur_us=(t1 - t0) * 1e6, trace=trace, args=args)

    def span(
        self,
        name: str,
        *,
        cat: str = "serve",
        trace: str | None = None,
        args: dict | None = None,
    ) -> Span:
        """``span(name, tracer=self, ...)`` with an explicit category."""
        return Span(name, self, trace, dict(args) if args else {}, cat=cat)

    def ingest(self, events: Iterable[dict]) -> None:
        """Adopt spans recorded by another process (they keep their pid)."""
        with self._lock:
            for ev in events:
                self._events.append(dict(ev))

    def drain(self) -> list[dict]:
        """Return-and-clear spans not yet shipped (worker → wire)."""
        with self._lock:
            out = list(self._pending)
            self._pending.clear()
        return out

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def export(self, path: str) -> int:
        """Write Chrome trace JSON; returns the number of events written.

        Load the file in ``chrome://tracing`` or https://ui.perfetto.dev.
        """
        evs = self.events()
        doc = {"traceEvents": evs, "displayTimeUnit": "ms"}
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return len(evs)
