"""Operations and bytes the algorithms need, computed from shapes.

These are the yardstick's counts: a kernel's roofline share and the
step's model FLOP utilisation divide them by measured device time, so
they count what the mathematics requires, never what a particular
program happens to execute (no recomputation, no padding, no second
backward pass for a gradient the scores already hold).
"""
from __future__ import annotations


def _attn_layer(d, f, Tq, Tk, *, causal, cross_kv_tokens=None):
    """Forward FLOPs of one transformer layer on one sequence."""
    proj = 2 * Tq * d * d * 4                       # q, k, v, o
    pairs = Tq * (Tq + 1) / 2 if causal else Tq * Tk
    attn = 2 * 2 * pairs * d                        # QKᵀ and PV
    ffn = 2 * 2 * Tq * d * f                        # up and down
    out = proj + attn + ffn
    if cross_kv_tokens is not None:
        Te = cross_kv_tokens
        out += 2 * Tq * d * d * 2                   # cross q, o
        out += 2 * Te * d * d * 2                   # cross k, v
        out += 2 * 2 * Tq * Te * d                  # cross QKᵀ and PV
    return out


def encdec_forward_flops(c, *, seq: int) -> float:
    """Forward FLOPs of one whisper-style sample: encoder over
    ``max_source_positions`` frames, decoder over ``seq`` tokens with
    cross-attention, and the tied unembedding over the vocabulary."""
    d, Te, V = c["d_model"], c["max_source_positions"], c["vocab_size"]
    enc = c["encoder_layers"] * _attn_layer(
        d, c["encoder_ffn_dim"], Te, Te, causal=False)
    dec = c["decoder_layers"] * _attn_layer(
        d, c["decoder_ffn_dim"], seq, seq, causal=True, cross_kv_tokens=Te)
    return float(enc + dec + 2 * seq * d * V)


def ngd_step_flops(c, *, n: int, seq: int) -> float:
    """One NGD step on n samples of m parameters: per-sample forward and
    backward (3× forward), the n×n Gram 2n²m, its Cholesky n³/3, and the
    two passes over the scores (S·v and Sᵀ·w, 2nm each)."""
    m = c["num_parameters"]
    score = 3 * n * encdec_forward_flops(c, seq=seq)
    return float(score + 2 * n * n * m + n ** 3 / 3 + 4 * n * m)


def chol_solve_flops(n: int, m: int, k: int = 1) -> float:
    """Algorithm 1 on S (n, m) with k right-hand sides: Gram 2n²m,
    Cholesky n³/3, S·V 2nmk, two triangular solves 2n²k, Sᵀ·w 2nmk."""
    return float(2 * n * n * m + n ** 3 / 3 + 4 * n * m * k + 2 * n * n * k)


def chol_solve_bytes(n: int, m: int, itemsize: int = 4) -> float:
    """Least HBM traffic of one solve: S read twice (the Gram pass and
    the fused S·v/Sᵀ·w passes cannot share one read across the factor)."""
    return float(2 * n * m * itemsize)


def window_pass_bytes(n: int, m: int, k: int, itemsize: int = 4,
                      chips: int = 1) -> float:
    """Least HBM traffic of one served microbatch on each chip: one read
    of its share of the resident window, plus reading V and writing X
    ((m/chips)·k each)."""
    mc = m / chips
    return float(n * mc * itemsize + 2 * mc * k * 4)
