"""Plain float32 reference of the dense decoder as the configuration
states it: pre-norm causal GQA self-attention with rotary embedding and a
SwiGLU MLP per layer, RMS norms, and a tied output head.  No cache, no
batching, no kernels: one sequence, one layer at a time.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from chipbench.reference.common import (Keys, attention_block, attn_params,
                                        f32, frozen, head_logits,
                                        mlp_block, mlp_params, param,
                                        weight_key)


def layer_params(key, m, dtype):
    keys = Keys(key)
    return {"attn": attn_params(Keys(keys.next()), m, dtype),
            "mlp": mlp_params(Keys(keys.next()), m, dtype)}


def layer(p, x, m, mode):
    return mlp_block(p["mlp"], attention_block(p["attn"], x, m, mode), m,
                     mode)


def logits(m: dict, seed: int, tokens, mode: str = "f32"):
    """(B, L) token ids -> (B, L, V) float32 logits, weights from
    ``seed``."""
    dtype = jnp.dtype(m["param_dtype"])
    root = Keys(weight_key(seed))
    embed = param(root, "embed", (m["vocab_size"], m["d_model"]), dtype)
    final_ln = param(root, "ones", (m["d_model"],), dtype)
    k_layers = root.next()
    make, run, head = _programs(frozen(m), mode)
    x = embed.astype(jnp.float32)[jnp.asarray(tokens)]
    for k in jax.random.split(k_layers, m["num_layers"]):
        x = run(make(k), x)
    return head(x, final_ln.astype(jnp.float32), embed.astype(jnp.float32))


@lru_cache(maxsize=None)
def _programs(fm, mode):
    """The jitted pieces, each over a batch of sequences."""
    m = dict(fm)
    dtype = jnp.dtype(m["param_dtype"])
    return (jax.jit(lambda k: f32(layer_params(k, m, dtype))),
            jax.jit(jax.vmap(partial(layer, m=m, mode=mode),
                             in_axes=(None, 0))),
            jax.jit(jax.vmap(partial(head_logits, m=m, mode=mode),
                             in_axes=(0, None, None))))
