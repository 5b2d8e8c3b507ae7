"""From a JAX profiler trace to the numbers the per-layer metrics read.

``Capture`` runs the window under ``jax.profiler`` into a fixed directory
of the checkout and ``reduce`` turns the ``.xplane.pb`` it wrote into a
``Reduced``: the union of the intervals in which an operation ran on each
device (busy time), the device time of each XLA program (module) by name,
the device time of each operation by name, and the gaps in which the
device was idle, each labelled with what the host was doing then.

Device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one
event per operation and their ``XLA Modules`` line one event per program
execution. Host spans (``TraceAnnotation``) are on ``/host:CPU``.
"""
from __future__ import annotations

import glob
import re
import shutil
import time
from collections import defaultdict
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """Per op name, the time its events ran minus the time of the events
    nested inside them (a ``while`` holds its body's ops)."""
    out = defaultdict(float)
    stack = []                      # [end, name, child time]
    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            end, nm, child = stack.pop()
            out[nm] -= child
        if stack:
            stack[-1][2] += e - s
        out[name] += e - s
        stack.append([e, name, 0])
    for end, nm, child in stack:
        out[nm] -= child
    return out


def short_op(name: str, width: int = 160) -> str:
    return name if len(name) <= width else name[:width - 3] + "..."


def module_base(name: str) -> str:
    """``jit_train_step(12)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


class Reduced:
    """What one traced window reads, averaged over the chips used."""

    def __init__(self, *, window_s, busy_s, module_s, module_n, op_s,
                 gaps, host_spans, chips):
        self.window_s = window_s          # host seconds the trace covered
        self.busy_s = busy_s              # mean over chips of the busy union
        self.module_s = module_s          # program name -> device s (per chip)
        self.module_n = module_n          # program name -> executions (per chip)
        self.op_s = op_s                  # op name -> device s (per chip)
        self.gaps = gaps                  # [(seconds, host label)] longest first
        self.host_spans = host_spans      # span name -> host s
        self.chips = chips

    def programs_s(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for n, s in self.module_s.items() if rx.search(n))

    def programs_n(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(c for n, c in self.module_n.items() if rx.search(n))

    def ops_s(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_s.items() if rx.search(n))

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[short_op(n), s] for n, s in top],
                "idle_gaps": [[label, s] for s, label in self.gaps[:10]]}


def reduce_xplane(path: str, *, window_s: float, chips: int) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    busy, module_s, module_n, op_s = [], defaultdict(float), \
        defaultdict(int), defaultdict(float)
    dev_intervals = []
    host = []
    n_dev = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                not plane.name.endswith("SparseCore"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            n_dev += 1
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in lines[OPS_LINE].events]
            for name, t in _self_times(evs).items():
                op_s[name] += t * 1e-9
            merged = _union([(s, e) for s, e, _ in evs])
            busy.append(sum(e - s for s, e in merged) * 1e-9)
            if n_dev == 1:
                dev_intervals = merged
            if MODULES_LINE in lines:
                for ev in lines[MODULES_LINE].events:
                    base = module_base(ev.name)
                    module_s[base] += ev.duration_ns * 1e-9
                    module_n[base] += 1
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for ev in ln.events:
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    if n_dev == 0:
        raise RuntimeError(f"no device plane with '{OPS_LINE}' in {path}")
    per = 1.0 / n_dev
    module_s = {k: v * per for k, v in module_s.items()}
    module_n = {k: int(round(v * per)) for k, v in module_n.items()}
    op_s = {k: v * per for k, v in op_s.items()}

    host_spans = defaultdict(float)
    for s, e, name in host:
        if name.startswith("bench."):
            host_spans[name] += (e - s) * 1e-9
    gaps = []
    for (s0, e0), (s1, _) in zip(dev_intervals, dev_intervals[1:]):
        if s1 - e0 > 0:
            gaps.append((e0, s1))
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [((g1 - g0) * 1e-9, _host_label(host, g0, g1))
                for g0, g1 in gaps[:10]]
    return Reduced(window_s=window_s, busy_s=sum(busy) / len(busy),
                   module_s=module_s, module_n=module_n, op_s=op_s,
                   gaps=labelled, host_spans=dict(host_spans), chips=n_dev)


def _host_label(host, g0, g1) -> str:
    """The benchmark span covering most of the gap, else the host event
    that does; 'host idle' when nothing on the host overlaps it."""
    best, best_ov = None, 0
    best_b, best_b_ov = None, 0
    for s, e, name in host:
        ov = min(e, g1) - max(s, g0)
        if ov <= 0:
            continue
        if name.startswith("bench."):
            if ov > best_b_ov:
                best_b, best_b_ov = name, ov
        elif ov > best_ov and (e - s) < 10 * (g1 - g0) + 1:
            best, best_ov = name, ov
    if best_b is not None:
        return best_b if best is None else f"{best_b}: {best}"
    return best if best is not None else "host idle"


class Capture:
    """The profiler around the measured window, into a fixed directory."""

    def __init__(self, directory: Path):
        self.dir = Path(directory)
        self.window_s = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        # device and host (TraceMe) activity; no per-call Python tracing,
        # which would slow the host path the serving cells measure
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self):
        import jax
        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()

    def path(self) -> str:
        found = sorted(glob.glob(str(self.dir / "plugins" / "profile" / "*"
                                     / "*.xplane.pb")))
        if not found:
            raise RuntimeError(f"no .xplane.pb under {self.dir}")
        return found[-1]

    def reduce(self, ctx, cell) -> Reduced:
        return reduce_xplane(self.path(), window_s=self.window_s,
                             chips=ctx.chips)
