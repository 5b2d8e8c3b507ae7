"""``ServeState`` — the serving subsystem's resident, checkpointable asset.

What a serving process keeps warm between requests is exactly the paper's
factorization, held open: the n-sample score window S, its undamped Gram
W, and the Cholesky factor L of W + (λ₀+jitter)Ĩ at the resident damping.
All of it is a flat NamedTuple pytree of arrays, so it jits, shards (see
``launch/shardings.py`` — replicated, like the training-side
``CurvatureState``), and round-trips through ``repro.checkpoint`` bit-
identically: a restarted server resumes with the same factor and produces
the same solves.

The request path reads this state (``SolveServer``); the online-adaptation
loop advances it by rank-k window algebra (``OnlineAdaptation``); nothing
on the request path ever rebuilds W from scratch.
"""
from __future__ import annotations

import hashlib
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.operator import BlockedScores, is_blocked
from repro.core.solvers import CholFactorization, chol_factorize, gram

__all__ = ["ServeStats", "ServeState", "init_serve_state", "serve_mode",
           "as_factorization", "save_serve_state", "restore_serve_state",
           "serve_state_arrays", "serve_state_from_arrays"]

_HI = jax.lax.Precision.HIGHEST


class ServeStats(NamedTuple):
    """Counters carried with the state (and therefore checkpointed)."""
    served: jax.Array          # requests completed
    microbatches: jax.Array    # coalesced solves executed
    adapted: jax.Array         # sample rows folded into the window
    refreshes: jax.Array       # full W refactorizations
    last_residual: jax.Array   # last monitored relative residual (−1: none)


class ServeState(NamedTuple):
    """The resident curvature window + factorization (a pytree).

    ``S``: the (n, m) sample-score window — dense array or a
    ``BlockedScores`` operator (itself a registered pytree).
    ``W``: undamped Gram of S. ``L``: chol(W + (lam0+jitter)Ĩ) — the
    resident factor at the server's base damping ``lam0``. ``slot``: next
    FIFO window row the adaptation loop will replace. ``age``:
    microbatches since the last full refresh.
    """
    S: Any
    W: jax.Array
    L: jax.Array
    lam0: jax.Array
    slot: jax.Array
    age: jax.Array
    stats: ServeStats

    def fingerprint(self, *, full: bool = True) -> str:
        """blake2b digest of the window/W/L buffers (shape+dtype tagged).

        The maintained-factor identity in hashable form: two states whose
        journals diverged by even one fold hash differently, while a
        checkpoint round-trip (or a bit-identical journal replay) hashes
        the same. Pulls the buffers to host — call it only at sites that
        already synchronized (flush end, maybe_refresh, checkpoint), the
        same contract as the health gauges. ``age``/``stats`` are
        deliberately excluded: they advance outside the fold journal, and
        the fingerprint's job is to witness the *factor*, not traffic
        accounting.

        ``full=False`` hashes only W and L — O(n²) bytes instead of the
        O(n·m) window, cheap enough for the flight recorder's cadenced
        tick. Every fold and refresh rewrites L, so the light digest
        still witnesses any factor divergence; the full one (the
        incident bundle's bit-identity target) additionally pins the
        window bytes. The two kinds hash into disjoint spaces (the
        mode tag below), so a light digest never equals a full one.
        """
        h = hashlib.blake2b(digest_size=16)
        h.update(b"full" if full else b"light")
        if full:
            arrs = (*(self.S.blocks if is_blocked(self.S) else (self.S,)),
                    self.W, self.L)
        else:
            arrs = (self.W, self.L)
        for arr in arrs:
            a = np.ascontiguousarray(np.asarray(jax.device_get(arr)))
            h.update(str(a.shape).encode())
            h.update(str(a.dtype).encode())
            h.update(a.view(np.uint8).reshape(-1))
        return h.hexdigest()


def _zero_stats() -> ServeStats:
    z = jnp.zeros((), jnp.int32)
    return ServeStats(served=z, microbatches=z, adapted=z, refreshes=z,
                      last_residual=-jnp.ones((), jnp.float32))


def init_serve_state(S, damping, *, jitter: float = 0.0,
                     mode: str = "auto",
                     window_dtype=None) -> ServeState:
    """Build the resident state: one O(n²·m) Gram pass + O(n³) Cholesky —
    the only time the serving subsystem ever pays them up front.

    ``window_dtype``: optional low-precision storage dtype for the score
    window (e.g. ``jnp.bfloat16``). The window is rounded to it *first*
    and W/L are built from the rounded values with fp32 accumulation, so
    the resident factor describes exactly the window the request path and
    the fold algebra will read — storage narrows, arithmetic never does.
    Real windows only (a complex window must realify via
    ``mode="real_part"`` before the cast).
    """
    if window_dtype is None:
        fac = chol_factorize(S, damping, mode=mode, jitter=jitter)
        return ServeState(S=fac.S, W=fac.W, L=fac.L, lam0=fac.lam,
                          slot=jnp.zeros((), jnp.int32),
                          age=jnp.zeros((), jnp.int32),
                          stats=_zero_stats())
    wd = jnp.dtype(window_dtype)
    if not jnp.issubdtype(wd, jnp.floating):
        raise ValueError(f"window_dtype must be a real float dtype, got {wd}")
    if jnp.issubdtype(S.dtype, jnp.complexfloating) and mode != "real_part":
        raise ValueError(
            "low-precision window storage is real-only; use "
            "mode='real_part' (realification) for a complex score window")
    # realify through the standard transform, round the window to the
    # storage dtype, then build W (fp32-accumulated Gram of the *stored*
    # values) and the resident factor from the rounded window.
    S_in = S
    if jnp.issubdtype(S_in.dtype, jnp.complexfloating):
        S_in = S_in.realify() if is_blocked(S_in) else \
            jnp.concatenate([jnp.real(S_in), jnp.imag(S_in)], axis=0)
    S_store = S_in.astype(wd)
    W = S_store.gram() if is_blocked(S_store) else gram(
        S_store.astype(jnp.promote_types(wd, jnp.float32)), precision=_HI)
    lam = jnp.asarray(damping, W.dtype)
    n = W.shape[0]
    L = jnp.linalg.cholesky(
        W + (lam + jnp.asarray(jitter, W.dtype)) * jnp.eye(n, dtype=W.dtype))
    return ServeState(S=S_store, W=W, L=L, lam0=lam,
                      slot=jnp.zeros((), jnp.int32),
                      age=jnp.zeros((), jnp.int32),
                      stats=_zero_stats())


def serve_mode(state: ServeState) -> str:
    """The resolved solver mode of the resident window (realification
    happened at ``init_serve_state``; only real/complex remain)."""
    return "complex" if jnp.issubdtype(state.S.dtype, jnp.complexfloating) \
        else "real"


def as_factorization(state: ServeState, *, jitter: float = 0.0,
                     precision=_HI) -> CholFactorization:
    """View the resident state as a ``CholFactorization`` — every solver
    affordance (multi-RHS ``solve``, ``with_damping``, ``solve_batch``,
    rank-k ``update``/``downdate``) then applies to the serving window."""
    return CholFactorization(S=state.S, mode=serve_mode(state), W=state.W,
                             L=state.L, lam=state.lam0, jitter=jitter,
                             take_real_v=False, precision=precision)


def save_serve_state(ckpt_dir, step: int, state: ServeState, *,
                     metadata: Optional[dict] = None, keep: int = 3):
    """Checkpoint the state (atomic, keep-last-k — see repro.checkpoint)."""
    from repro.checkpoint import checkpoint as ckpt
    meta = {"kind": "serve_state",
            "blocked": bool(is_blocked(state.S)),
            **(metadata or {})}
    return ckpt.save(ckpt_dir, step, state, metadata=meta, keep=keep)


def restore_serve_state(ckpt_dir, step: int, like: ServeState):
    """Restore into the structure of ``like`` (e.g. a freshly initialized
    state of the same shapes). Returns (state, metadata)."""
    from repro.checkpoint import checkpoint as ckpt
    return ckpt.restore(ckpt_dir, step, like)


def _npz_safe(arr: np.ndarray) -> Tuple[np.ndarray, str]:
    """numpy can't round-trip ml_dtypes through .npy — store bf16 as a
    uint16 view and remember the logical dtype (same trick as
    ``repro.checkpoint``)."""
    dtype = str(arr.dtype)
    if dtype == "bfloat16":
        return arr.view(np.uint16), dtype
    return arr, dtype


def serve_state_arrays(state: ServeState) -> Tuple[dict, dict]:
    """Flatten a ``ServeState`` to named host arrays + a JSON-safe meta
    dict — the self-describing form the flight recorder's incident
    bundles use (``repro.checkpoint.restore`` needs a ``like`` template;
    an offline forensics run has none). Inverse:
    ``serve_state_from_arrays``."""
    blocks = state.S.blocks if is_blocked(state.S) else (state.S,)
    names = list(state.S.names) if is_blocked(state.S) \
        and state.S.names is not None else None
    arrays: dict = {}
    dtypes: dict = {}

    def put(key, leaf):
        a, dtypes[key] = _npz_safe(np.asarray(jax.device_get(leaf)))
        arrays[key] = a

    for i, b in enumerate(blocks):
        put(f"S{i}", b)
    put("W", state.W)
    put("L", state.L)
    put("lam0", state.lam0)
    put("slot", state.slot)
    put("age", state.age)
    for f, v in zip(state.stats._fields, state.stats):
        put(f"stats_{f}", v)
    meta = {"blocked": bool(is_blocked(state.S)),
            "n_blocks": len(blocks), "names": names, "dtypes": dtypes}
    return arrays, meta


def serve_state_from_arrays(arrays: dict, meta: dict) -> ServeState:
    """Rebuild a ``ServeState`` from ``serve_state_arrays`` output."""
    def get(key):
        a = np.asarray(arrays[key])
        if meta["dtypes"].get(key) == "bfloat16":
            import ml_dtypes
            a = a.view(ml_dtypes.bfloat16)
        return jnp.asarray(a)

    blocks = tuple(get(f"S{i}") for i in range(int(meta["n_blocks"])))
    names = meta.get("names")
    S = BlockedScores(blocks, names=tuple(names) if names else None) \
        if meta["blocked"] else blocks[0]
    stats = ServeStats(**{f: get(f"stats_{f}") for f in ServeStats._fields})
    return ServeState(S=S, W=get("W"), L=get("L"), lam0=get("lam0"),
                      slot=get("slot"), age=get("age"), stats=stats)
