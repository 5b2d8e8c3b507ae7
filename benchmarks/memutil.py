"""Compiled peak-memory probes shared by the serving benchmarks.

Every ``BENCH_serve.json`` row carries a ``peak_mem_bytes`` field; the
number that matters for the serving sections is the compiled footprint of
the request path itself — the width-k coalesced solve against the
resident window, where the (m, k) RHS/solution buffers riding next to
the (n, m) window dominate and everything else is n-sized.
``serve_request_peak_bytes`` lowers exactly the jitted entry the
``SolveServer`` dispatches (``serve.server._coalesced_solve``) and reads
XLA's ``memory_analysis`` (transient temps + arguments + outputs).
Backends without the analysis fall back to ``cost_analysis``'s
``bytes accessed`` estimate, and backends with neither report ``None``,
so rows stay null rather than carry a made-up number.
"""
from __future__ import annotations

__all__ = ["compiled_bytes", "lowered_peak_bytes", "peak_for_row",
           "serve_request_peak_bytes"]


def compiled_bytes(compiled):
    """Peak bytes of a compiled executable: temps + arguments + outputs
    from ``memory_analysis``, else ``cost_analysis``'s ``bytes accessed``,
    else ``None``."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        try:
            return int(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                       + ma.output_size_in_bytes)
        except (AttributeError, TypeError):
            pass
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, dict) and ca.get("bytes accessed"):
        return int(ca["bytes accessed"])
    return None


def lowered_peak_bytes(jitted, *args, **kwargs):
    """Peak compiled bytes of ``jitted(*args, **kwargs)``; ``None`` when
    the backend offers no analysis (or lowering itself fails)."""
    try:
        return compiled_bytes(jitted.lower(*args, **kwargs).compile())
    except Exception:
        return None


def serve_request_peak_bytes(n, m, k, *, damping=1e-2, window_dtype=None,
                             fused=True, seed=0, **_ignored):
    """Compiled peak of the serving fast path: the uniform-λ width-``k``
    coalesced solve against a random (n, m) resident window, storage in
    ``window_dtype`` (None: fp32). Extra shape kwargs (``requests``, …)
    are accepted and ignored so bench shape dicts pass through whole."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.server import _coalesced_solve
    from repro.serve.state import init_serve_state

    rng = np.random.default_rng(seed)
    S = jnp.asarray(rng.normal(size=(n, m)) / np.sqrt(m), jnp.float32)
    state = init_serve_state(S, damping, window_dtype=window_dtype)
    V = jnp.zeros((m, k), jnp.float32)
    lams = jnp.full((k,), damping, jnp.float32)
    return lowered_peak_bytes(
        _coalesced_solve, state.S, state.W, state.L, state.lam0, V, lams,
        mode="real", jitter=0.0, uniform=True, monitor=False,
        refactorize=False, fused=fused)


def peak_for_row(name, peaks):
    """Pick the peak for a bench row: dtype-suffixed rows get their own
    dtype's number, everything else the fp32 one. ``peaks`` maps
    ``"fp32"``/``"bf16"`` to bytes (or None)."""
    if not peaks:
        return None
    return peaks.get("bf16") if name.endswith("_bf16") else \
        peaks.get("fp32")
