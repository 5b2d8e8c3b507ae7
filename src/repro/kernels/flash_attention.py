"""Pallas TPU kernel: flash-attention forward (causal / windowed, GQA).

The attention score computation is the dominant FLOP term of every
assigned transformer architecture; this kernel gives it the canonical
TPU treatment:

* grid = (B·H, Tq/bq, Tk/bk) with the KV axis innermost and *sequential*;
  the (bq, hd) fp32 accumulator and the (bq,) running max / sum live in
  VMEM scratch that persists across the KV sweep (online softmax — HBM
  never sees a (Tq, Tk) tensor);
* GQA without materializing repeated KV: the K/V BlockSpec index maps
  divide the query-head grid index by the group size, so each KV head's
  tile is streamed once per query-head group directly from HBM;
* causal / sliding-window masking is applied from block-relative iotas,
  and fully-masked KV blocks are skipped with ``pl.when`` (≈2× fewer MXU
  passes for causal attention);
* bf16 QK/PV operands, fp32 softmax statistics — matching the framework's
  ``attn_bf16`` lever.

``ops.py`` routes to this kernel on TPU; the pure-jnp blockwise
implementation in ``models/layers.py`` (same math, validated against the
naive oracle) remains the CPU/compile-analysis path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

F32 = jnp.float32
NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window, bq: int, bk: int,
                  nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos0 = qi * bq
    k_pos0 = ki * bk
    # a KV block is live unless it is entirely above the causal diagonal
    # or entirely outside the sliding window
    live = True
    if causal:
        live = jnp.logical_and(live, k_pos0 <= q_pos0 + bq - 1)
    if window is not None:
        live = jnp.logical_and(live, k_pos0 + bk - 1 > q_pos0 - window)

    @pl.when(live)
    def _block():
        q = q_ref[0]                                   # (bq, hd)
        k = k_ref[0]                                   # (bk, hd)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=F32) * scale        # (bq, bk)

        q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])                # (bq, bk) f32
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=F32)                # (bq, hd)
        acc_scr[...] = corr[:, None] * acc_scr[...] + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _final():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "bq", "bk", "group", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None, scale=None,
                           group=1, bq=128, bk=128, interpret=False):
    """q: (BH, Tq, hd); k, v: (BKH, Tk, hd) with BH == BKH·group.

    Returns (BH, Tq, hd) in q.dtype. Tq % bq == Tk % bk == 0 (pad upstream;
    ops.py handles the padding and the (B, T, H, hd) layout).
    """
    BH, Tq, hd = q.shape
    BKH, Tk, _ = k.shape
    assert BH == BKH * group, (BH, BKH, group)
    assert Tq % bq == 0 and Tk % bk == 0, (Tq, bq, Tk, bk)
    scale = scale if scale is not None else hd ** -0.5
    nq, nk = Tq // bq, Tk // bk

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), F32),
            pltpu.VMEM((bq,), F32),
            pltpu.VMEM((bq, hd), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
