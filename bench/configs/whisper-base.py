"""Plain reference for the whisper-base configuration (``whisper-base.json``).

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no
kernels, no scan, no remat, no blockwise softmax. It follows the
equations the configuration file states (its ``departures`` list says
where they differ from the published model) and imports nothing of the
program under test. The parameter tree is described here from the
configuration's sizes; the benchmark checks it against the program's.

``rnd`` rounds every matmul operand before the product. The identity is
the reference; rounding through float8 is the control, the precision one
step below the configuration's bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def padded_vocab(cfg) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def param_shapes(cfg) -> dict:
    """The parameter tree as nested dicts/lists of shapes (leaves stacked
    over layers on axis 0)."""
    d, f = cfg["d_model"], cfg["encoder_ffn_dim"]

    def norm(R=None):
        s = (d,) if R is None else (R, d)
        return {"b": s, "g": s}

    def block(R, cross):
        p = {"norm": norm(R), "ffn_norm": norm(R),
             "wq": (R, d, d), "wk": (R, d, d), "wv": (R, d, d),
             "wo": (R, d, d), "w_up": (R, d, f), "w_down": (R, f, d)}
        if cross:
            p.update({"xnorm": norm(R), "xq": (R, d, d), "xk": (R, d, d),
                      "xv": (R, d, d), "xo": (R, d, d)})
        return p

    return {
        "enc_blocks": [block(cfg["encoder_layers"], False)],
        "enc_final_norm": norm(),
        "dec": {"embed": (padded_vocab(cfg), d), "final_norm": norm(),
                "pos_embed": (cfg["max_target_positions"], d),
                "blocks": [block(cfg["decoder_layers"], True)]},
    }


def init_leaf(path: str, shape, key):
    """Seeded initial value of one leaf, in float32: layer-norm gains 1,
    biases 0, embeddings N(0, 0.02²), matrices N(0, 1/fan_in)."""
    if path.endswith("['g']"):
        return jnp.ones(shape, F32)
    if path.endswith("['b']"):
        return jnp.zeros(shape, F32)
    if "embed" in path:
        return 0.02 * jax.random.normal(key, shape, F32)
    return jax.random.normal(key, shape, F32) / math.sqrt(shape[-2])


def _mm(rnd):
    def mm(eq, a, b):
        return jnp.einsum(eq, rnd(a), rnd(b), precision=HI,
                          preferred_element_type=F32)
    return mm


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _sinusoid(T, d):
    pos = jnp.arange(T, dtype=F32)[:, None]
    dim = jnp.arange(d // 2, dtype=F32)[None, :]
    ang = pos / (10000.0 ** (dim / (d // 2 - 1)))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _attend(mm, q, k, v, heads, causal):
    """q: (Tq, d), k/v: (Tk, d) -> (Tq, d); softmax over all keys."""
    Tq, d = q.shape
    hd = d // heads
    q = q.reshape(Tq, heads, hd)
    k = k.reshape(-1, heads, hd)
    v = v.reshape(-1, heads, hd)
    s = mm("qhe,khe->hqk", q, k) / math.sqrt(hd)
    if causal:
        keep = jnp.tril(jnp.ones((Tq, k.shape[0]), bool))
        s = jnp.where(keep[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm("hqk,khe->qhe", p, v).reshape(Tq, d)


def _layer(mm, p, x, cfg, *, heads, causal, enc=None):
    eps = cfg["layer_norm_eps"]
    h = _layer_norm(x, p["norm"], eps)
    a = _attend(mm, mm("td,de->te", h, p["wq"]), mm("td,de->te", h, p["wk"]),
                mm("td,de->te", h, p["wv"]), heads, causal)
    x = x + mm("td,de->te", a, p["wo"])
    if enc is not None:
        h = _layer_norm(x, p["xnorm"], eps)
        a = _attend(mm, mm("td,de->te", h, p["xq"]),
                    mm("td,de->te", enc, p["xk"]),
                    mm("td,de->te", enc, p["xv"]), heads, False)
        x = x + mm("td,de->te", a, p["xo"])
    h = _layer_norm(x, p["ffn_norm"], eps)
    return x + mm("tf,fd->td", _gelu_tanh(mm("td,df->tf", h, p["w_up"])),
                  p["w_down"])


def _take(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def sample_logp(params, ex, cfg, rnd=lambda a: a):
    """Σ_t mask_t · log P(label_t | inputs_<=t, frames) of one example."""
    mm = _mm(rnd)
    params = jax.tree.map(lambda a: a.astype(F32), params)
    eps = cfg["layer_norm_eps"]
    frames = ex["frames"].astype(F32)
    x = frames + _sinusoid(*frames.shape)
    enc_p = params["enc_blocks"][0]
    for i in range(cfg["encoder_layers"]):
        x = _layer(mm, _take(enc_p, i), x, cfg,
                   heads=cfg["encoder_attention_heads"], causal=False)
    enc = _layer_norm(x, params["enc_final_norm"], eps)

    dec = params["dec"]
    tokens = ex["inputs"]
    y = dec["embed"][tokens] + dec["pos_embed"][:tokens.shape[0]]
    dec_p = dec["blocks"][0]
    for i in range(cfg["decoder_layers"]):
        y = _layer(mm, _take(dec_p, i), y, cfg,
                   heads=cfg["decoder_attention_heads"], causal=True,
                   enc=enc)
    y = _layer_norm(y, dec["final_norm"], eps)
    logits = mm("td,vd->tv", y, dec["embed"][:cfg["vocab_size"]])
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, ex["labels"][:, None], axis=-1)[:, 0]
    return jnp.sum(picked * ex["mask"].astype(F32))


def batch_loss(params, batch, cfg, rnd=lambda a: a):
    """Mean next-token cross entropy over the batch's unmasked tokens."""
    logps = jax.lax.map(lambda ex: sample_logp(params, ex, cfg, rnd), batch)
    return -jnp.sum(logps) / jnp.maximum(jnp.sum(batch["mask"]), 1.0)
