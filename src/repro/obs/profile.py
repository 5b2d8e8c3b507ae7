"""Optional ``jax.profiler`` capture hooks around the coalesced solve.

Kernel-level drill-down for when the span tracer says "device solve"
is the slow stage but not *why*.  Every method is a no-op when profiling
is off, so the serving hot path carries a single ``if`` when disabled.
When a trace directory was asked for, a failed ``start_trace`` or
``stop_trace`` raises: a run that was meant to be profiled does not go
on without its trace.
"""

from __future__ import annotations

import contextlib

__all__ = ["ProfileHooks"]


class ProfileHooks:
    """Gated wrapper over ``jax.profiler`` trace + step annotations.

    ``ProfileHooks(log_dir)`` starts a profiler trace into ``log_dir``
    on ``start()`` and annotates each coalesced solve with a
    ``StepTraceAnnotation`` so devices steps line up in the viewer.
    With ``log_dir=None`` every method is a no-op.
    """

    def __init__(self, log_dir: str | None = None) -> None:
        self.log_dir = log_dir
        self._active = False

    def start(self) -> None:
        if self.log_dir is None or self._active:
            return
        import jax

        jax.profiler.start_trace(self.log_dir)
        self._active = True

    def stop(self) -> None:
        if not self._active:
            return
        import jax

        self._active = False
        jax.profiler.stop_trace()

    def step(self, name: str = "coalesced_solve", step: int | None = None):
        """Context manager annotating one solve; no-op when inactive."""
        if not self._active:
            return contextlib.nullcontext()
        import jax

        kwargs = {} if step is None else {"step_num": step}
        return jax.profiler.StepTraceAnnotation(name, **kwargs)
