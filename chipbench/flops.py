"""Model FLOPs per token, from a configuration's shapes alone.

Counts the multiply-adds a forward pass of a dense decoder needs (2 FLOPs
each): every weight matmul, the tied or separate output head, and
attention's scores and weighted values at the token's context.  Norms,
rotary embedding and activations are left out (well under 1% at these
widths).
Behind the ``mfu`` metric, so it is kept with the benchmark and not taken
from the program.
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matmul for every token: attention
    and MLP weights and the output head (the embedding lookup is no
    matmul)."""
    if m["arch_type"] != "dense":
        raise ValueError(f"no FLOP count for arch_type {m['arch_type']!r}")
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["num_heads"] * hd * 2 + d * m["num_kv_heads"] * hd * 2
    mlp = 3 * d * m["d_ff"]
    return m["num_layers"] * (attn + mlp) + m["vocab_size"] * d


def flops_per_token(m: dict, context: float) -> float:
    """FLOPs of one token's forward at ``context`` earlier positions (a
    sliding window caps what attention sees)."""
    win = m.get("sliding_window") or 0
    ctx = min(context, win) if win else context
    attn = m["num_layers"] * 4 * m["num_heads"] * m["head_dim"] * ctx
    return 2 * matmul_params(m) + attn


def flops_for_requests(m: dict, requests) -> float:
    """Forward FLOPs of serving ``requests``, each (prompt length, tokens
    produced): every prompt and every output position once, each at its
    own context (the sum of positions is closed-form).  The engine
    prefills each request's prompt itself, siblings of a group included,
    so each prompt counts once per request."""
    total = 0.0
    base = flops_per_token(m, 0)
    per_ctx = flops_per_token(m, 1) - base
    win = m.get("sliding_window") or 0
    for prompt, n in requests:
        L = prompt + n
        if win and L > win:
            pos = win * (win - 1) / 2 + (L - win) * win
        else:
            pos = L * (L - 1) / 2
        total += L * base + per_ctx * pos
    return total
