"""Model FLOPs per token, from a configuration's shapes alone.

Counts the multiply-adds a forward pass needs (2 FLOPs each): every
weight matmul, the tied or separate output head, and attention's scores
and weighted values at the token's context.  Norms, rotary embedding and
activations are left out (well under 1% at these widths).

The count takes the whole configuration file: its ``model`` block, and
its ``published`` block where a held share needs the published total.

* ``dense``: attention and a SwiGLU MLP in every layer.
* ``moe``: ``first_dense_layers`` dense layers, then in each MoE layer a
  router over every routed expert, ``num_shared_experts`` shared experts
  and ``moe_top_k`` routed ones, of which the share held here (the model
  block's ``num_experts`` of ``published.n_routed_experts``) is counted:
  the expected part of the top-k that falls on experts held here.
* latent attention (MLA), where the model block has ``kv_lora_rank``:
  the published projections (q, or q_a and q_b; kv_a; kv_b; o) and, per
  context position, scores over nope + rope and values over v.  This is
  the published form, not the absorbed decode form, so it can only
  understate what a kernel computes.

Any other ``arch_type`` is refused (:func:`check`).  Behind the ``mfu``
metric, so it is kept with the benchmark and not taken from the program.
"""
from __future__ import annotations

COUNTED = ("dense", "moe")


def check(conf: dict) -> None:
    """Raise ``ValueError`` unless the configuration can be counted."""
    m = conf["model"]
    if m["arch_type"] not in COUNTED:
        raise ValueError(f"{conf.get('name', '?')}: no FLOP count for "
                         f"arch_type {m['arch_type']!r} (counted: "
                         f"{', '.join(COUNTED)})")
    if m["arch_type"] == "moe" and m.get("moe_every", 1) != 1:
        raise ValueError(f"{conf.get('name', '?')}: no FLOP count for "
                         f"moe_every {m['moe_every']}")


def _head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def _mla(m: dict) -> bool:
    return bool(m.get("kv_lora_rank"))


def attention_params(m: dict) -> int:
    """One layer's attention projections."""
    d, h = m["d_model"], m["num_heads"]
    if _mla(m):
        nope, rope = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
        v, kv = m["v_head_dim"], m["kv_lora_rank"]
        qr = m.get("q_lora_rank")
        q = d * qr + qr * h * (nope + rope) if qr else d * h * (nope + rope)
        return q + d * (kv + rope) + kv * h * (nope + v) + h * v * d
    hd = _head_dim(m)
    return d * h * hd * 2 + d * m["num_kv_heads"] * hd * 2


def attention_flops_per_position(m: dict) -> int:
    """FLOPs one token spends per earlier position, over all layers:
    scores (query by key) and the weighted sum of values."""
    h = m["num_heads"]
    if _mla(m):
        qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
        v = m["v_head_dim"]
    else:
        qk = v = _head_dim(m)
    return m["num_layers"] * (2 * h * qk + 2 * h * v)


def ffn_params(conf: dict) -> float:
    """Every layer's feed-forward parameters a token passes through (a
    held share of the routed experts counts as its expected part)."""
    m = conf["model"]
    d, L = m["d_model"], m["num_layers"]
    if m["arch_type"] != "moe":
        return L * 3 * d * m["d_ff"]
    dense = m.get("first_dense_layers", 0)
    held = m["num_experts"]
    total = conf.get("published", {}).get("n_routed_experts") or held
    expert = 3 * d * m["moe_d_ff"]
    moe = d * total + m.get("num_shared_experts", 0) * expert \
        + m["moe_top_k"] * held * expert / total
    return dense * 3 * d * m["d_ff"] + (L - dense) * moe


def matmul_params(conf: dict) -> float:
    """Parameters that take part in a matmul for every token: attention
    and feed-forward weights and the output head (the embedding lookup is
    no matmul)."""
    check(conf)
    m = conf["model"]
    return (m["num_layers"] * attention_params(m) + ffn_params(conf)
            + m["vocab_size"] * m["d_model"])


def flops_per_token(conf: dict, context: float) -> float:
    """FLOPs of one token's forward at ``context`` earlier positions (a
    sliding window caps what attention sees)."""
    m = conf["model"]
    win = m.get("sliding_window") or 0
    ctx = min(context, win) if win else context
    return 2 * matmul_params(conf) + attention_flops_per_position(m) * ctx


def flops_for_requests(conf: dict, requests) -> float:
    """Forward FLOPs of serving ``requests``, each (prompt length, tokens
    produced): every prompt and every output position once, each at its
    own context (the sum of positions is closed-form).  The engine
    prefills each request's prompt itself, siblings of a group included,
    so each prompt counts once per request."""
    total = 0.0
    base = flops_per_token(conf, 0)
    per_ctx = flops_per_token(conf, 1) - base
    win = conf["model"].get("sliding_window") or 0
    for prompt, n in requests:
        L = prompt + n
        if win and L > win:
            pos = win * (win - 1) / 2 + (L - win) * win
        else:
            pos = L * (L - 1) / 2
        total += L * base + per_ctx * pos
    return total
