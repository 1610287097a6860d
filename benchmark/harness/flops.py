"""Operations and bytes worked out from shapes (the yardstick's arithmetic).

All take the model group of a configuration file: vocab_size, n_ctx,
n_embd, n_layer, n_head.
"""

from __future__ import annotations


def n_params(m: dict) -> int:
    """Parameters of a GPT-2 with a tied head: embeddings, per layer two
    LayerNorms, attention (qkv + proj) and MLP (fc + proj), final LayerNorm."""
    c, v, t, l = m["n_embd"], m["vocab_size"], m["n_ctx"], m["n_layer"]
    per_layer = (
        2 * 2 * c                # ln_1, ln_2: scale + bias
        + c * 3 * c + 3 * c      # c_attn
        + c * c + c              # c_proj
        + c * 4 * c + 4 * c      # mlp_fc
        + 4 * c * c + c          # mlp_proj
    )
    return v * c + t * c + l * per_layer + 2 * c


def train_flops_per_token(m: dict) -> float:
    """6·N: forward 2·N and backward 4·N per token; remat's recomputation
    and the attention-score products are not counted (so the share reads low,
    never high)."""
    return 6.0 * n_params(m)


def forward_flops_per_token(m: dict) -> float:
    """2·N per token computed (prefill or decode)."""
    return 2.0 * n_params(m)


def decode_step_bytes(m: dict, live_context_tokens: int, weight_bytes: int = 4,
                      cache_bytes: int = 4) -> float:
    """Bytes one decode step has to read: every weight once (the position
    table aside: one row per slot) and the keys and values of the live
    context once. `live_context_tokens` is the sum over live slots of their
    current lengths."""
    c, l = m["n_embd"], m["n_layer"]
    weights = (n_params(m) - m["n_ctx"] * c) * weight_bytes
    kv = 2 * l * live_context_tokens * c * cache_bytes
    return float(weights + kv)
