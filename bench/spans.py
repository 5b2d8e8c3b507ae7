"""The serving program's own spans, read from the traced window's capture.

The program writes its spans through ``repro.obs.span``: each is a
profiler TraceMe on the host plane of the ``.xplane.pb``, on the clock of
the device's operations, with its arguments as metadata (``serve.*`` and
``adapt.*`` stages, ``host.sync`` reads; uid lists joined by spaces).
``load`` finds the capture ``bench/run.py`` wrote for the cell under
``artifacts/bench_trace/<workload>``, parses it once per run and keeps
those spans with the first device's busy intervals. A capture without
such spans (a program that writes none) gives an empty list, and the
readers of these spans then return nothing.
"""
from __future__ import annotations

import bisect
import glob
from pathlib import Path
from typing import NamedTuple

TRACE_DIR = Path(__file__).resolve().parents[1] / "artifacts" / "bench_trace"
PREFIXES = ("serve.", "adapt.", "host.")


class Span(NamedTuple):
    name: str
    start: int          # ns, the capture's clock
    end: int
    args: dict
    line: int           # host-plane line (one per thread)


class Spans:
    """The program's spans and the first device's busy union (ns)."""

    def __init__(self, spans, busy):
        self.spans = sorted(spans, key=lambda s: s.start)
        self.busy = busy
        self._starts = [a for a, _ in busy]

    def named(self, name: str):
        return [s for s in self.spans if s.name == name]

    def busy_in(self, s: int, e: int) -> int:
        """Nanoseconds of [s, e) in which the device was busy."""
        i = max(bisect.bisect_right(self._starts, s) - 1, 0)
        out = 0
        while i < len(self.busy) and self.busy[i][0] < e:
            a, b = self.busy[i]
            out += max(0, min(e, b) - max(s, a))
            i += 1
        return out


def uids(span: Span):
    """A dispatch's request uids (one uid reads back as a number)."""
    return [int(u) for u in str(span.args.get("uids", "")).split()]


def from_xplane(path: str, union) -> Spans:
    """Parse one capture; ``union`` merges intervals (``bench/trace.py``)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, busy = [], None
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for i, ln in enumerate(plane.lines):
                for ev in ln.events:
                    if ev.name.startswith(PREFIXES):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          {k: v for k, v in ev.stats}, i))
        elif busy is None and plane.name.startswith("/device:TPU:") and \
                not plane.name.endswith("SparseCore"):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    busy = union([(ev.start_ns, ev.start_ns + ev.duration_ns)
                                  for ev in ln.events])
    return Spans(spans, busy or [])


def load(ctx) -> Spans | None:
    """The cell's capture, parsed once per run; None without one."""
    cache = ctx.__dict__.setdefault("_spans", {})
    if "spans" not in cache:
        found = sorted(glob.glob(str(TRACE_DIR / ctx.workload / "plugins"
                                     / "profile" / "*" / "*.xplane.pb")))
        cache["spans"] = from_xplane(found[-1], ctx.module("trace")._union) \
            if found else None
    return cache["spans"]

