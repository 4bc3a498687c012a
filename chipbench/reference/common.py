"""Plain float32 building blocks of the references, and their weights.

The references import nothing of the program.  Their weights are made
from the seed the way the configuration states them: a chain of keys
split from ``PRNGKey(seed)``, one key per parameter in declaration order,
stacked layers drawn from keys split off one key, normal draws in the
served dtype (bfloat16) scaled by ``1/sqrt(fan_in)`` unless a parameter
says otherwise, the embedding at 0.02, norms at one and biases at zero.
Each reference walks its own declaration order and draws one layer at a
time, so a large model never has to sit in memory whole.

``mm`` is the one matmul of a reference.  In ``f32`` it runs at the
highest precision; in ``fp8`` both operands are rounded to float8 e4m3
first (weights per output column, activations per row, each scaled to
the format's largest finite value), which is the control that must fail
the comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn


def weight_key(seed: int):
    """The root key of a run's weights: PRNGKey of the seed's low 32
    bits (``--seed`` may exceed what a key's word holds)."""
    return jax.random.PRNGKey(seed % (1 << 32))


class Keys:
    """One parameter key per call, split off a running key."""

    def __init__(self, key):
        self.key = key

    def next(self):
        self.key, sub = jax.random.split(self.key)
        return sub


def param(keys: Keys, kind: str, shape, dtype, scale=None):
    """The next parameter of a declaration: ``normal``, ``embed``,
    ``zeros`` or ``ones``.  Every kind takes a key."""
    k = keys.next()
    if kind == "normal":
        s = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return jax.random.normal(k, shape, dtype) * s
    if kind == "embed":
        return jax.random.normal(k, shape, dtype) * 0.02
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    raise ValueError(kind)


def frozen(m: dict):
    """A model block as a hashable key (for caching compiled pieces)."""
    return tuple(sorted(m.items()))


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def quant8(x, axis):
    """x rounded to float8 e4m3, scaled along ``axis`` to the format."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(x, w, mode: str):
    """x (..., k) @ w (k, n) in float32, highest precision."""
    if mode == "fp8":
        x, w = quant8(x, -1), quant8(w, 0)
    elif mode != "f32":
        raise ValueError(mode)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotary embedding, rotate-half layout. x (L, H, D), pos (L,)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def attention_block(p, x, m, mode):
    """Pre-norm causal GQA self-attention with rotary embedding and an
    optional sliding window; x (L, d) -> x + attention."""
    L = x.shape[0]
    H, Hk, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    xn = rms_norm(x, p["ln"], m["rms_eps"])
    pos = jnp.arange(L)
    q = rope(mm(xn, p["wq"], mode).reshape(L, H, D), pos, m["rope_theta"])
    k = rope(mm(xn, p["wk"], mode).reshape(L, Hk, D), pos, m["rope_theta"])
    v = mm(xn, p["wv"], mode).reshape(L, Hk, D)
    k = jnp.repeat(k, H // Hk, axis=1)
    v = jnp.repeat(v, H // Hk, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / math.sqrt(D)
    allowed = pos[None, :] <= pos[:, None]
    win = m.get("sliding_window") or 0
    if win:
        allowed &= pos[None, :] > pos[:, None] - win
    s = jnp.where(allowed[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return x + mm(o.reshape(L, H * D), p["wo"], mode)


def mlp_block(p, x, m, mode):
    """Pre-norm SwiGLU MLP; x (L, d) -> x + MLP."""
    xn = rms_norm(x, p["ln"], m["rms_eps"])
    h = jax.nn.silu(mm(xn, p["wg"], mode)) * mm(xn, p["wu"], mode)
    return x + mm(h, p["wd"], mode)


def attn_params(keys: Keys, m, dtype):
    d, H, Hk, D = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    return {"ln": param(keys, "ones", (d,), dtype),
            "wq": param(keys, "normal", (d, H * D), dtype),
            "wk": param(keys, "normal", (d, Hk * D), dtype),
            "wv": param(keys, "normal", (d, Hk * D), dtype),
            "wo": param(keys, "normal", (H * D, d), dtype,
                        scale=1.0 / math.sqrt(H * D))}


def mlp_params(keys: Keys, m, dtype):
    d, f = m["d_model"], m["d_ff"]
    return {"ln": param(keys, "ones", (d,), dtype),
            "wg": param(keys, "normal", (d, f), dtype),
            "wu": param(keys, "normal", (d, f), dtype),
            "wd": param(keys, "normal", (f, d), dtype,
                        scale=1.0 / math.sqrt(f))}


def head_logits(x, final_ln, embed, m, mode):
    """Final norm and the tied output head: (L, d) -> (L, V)."""
    return mm(rms_norm(x, final_ln, m["rms_eps"]), embed.T, mode)

