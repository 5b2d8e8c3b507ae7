"""Optional ``jax.profiler`` capture for ``--profile-dir``.

Kernel-level drill-down: the capture holds the device's operations and,
on the same clock, the spans the program always writes (``obs.trace``:
``serve.microbatch`` step spans, ``serve.dispatch``, ``adapt.refresh``,
``host.sync`` ...). Every method is a no-op when profiling is off. When a
trace directory was asked for, a failed ``start_trace`` or
``stop_trace`` raises: a run that was meant to be profiled does not go
on without its trace.
"""

from __future__ import annotations

__all__ = ["ProfileHooks"]


class ProfileHooks:
    """Gated ``jax.profiler`` capture.

    ``ProfileHooks(log_dir)`` starts a profiler trace into ``log_dir``
    on ``start()`` and stops it on ``stop()``. With ``log_dir=None``
    every method is a no-op.
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
