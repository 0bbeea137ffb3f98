"""Operations and bytes of a language model's serving work, from shapes.

Counts are what the algorithm needs, whatever a path happens to do: a
matrix product of (m, k) by (k, n) is 2mkn operations; causal prefill
attention scores S(S+1)/2 query-key pairs; a mixture-of-experts layer
runs each token through its top-k experts only; a decode step reads
each weight once, the experts that its tokens route to, and the valid
KV rows of each active slot. All inputs are the configuration file's
keys (``bench/configs/<name>.json``), never the program's.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(c: dict):
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    return d, h, kv, c["head_dim"], c["num_hidden_layers"], c["vocab_size"]


def _moe(c: dict):
    return c.get("num_local_experts"), c.get("num_experts_per_tok")


def attn_weight_params(c: dict) -> int:
    """Per layer: q, k, v and output projections."""
    d, h, kv, hd, _, _ = _dims(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def mlp_weight_params(c: dict, experts: float | None = None) -> float:
    """Per layer: the gated MLP, or ``experts`` of the expert MLPs plus
    the router."""
    d, f = c["hidden_size"], c["intermediate_size"]
    e, _ = _moe(c)
    if e is None:
        return 3 * d * f
    return (e if experts is None else experts) * 3 * d * f + d * e


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def token_linear_flops(c: dict) -> int:
    """One token through every layer's projections and MLP (top-k
    experts), without attention scores and without the head."""
    d, f = c["hidden_size"], c["intermediate_size"]
    e, k = _moe(c)
    mlp = 2 * 3 * d * f * (k if e else 1) + (2 * d * e if e else 0)
    return c["num_hidden_layers"] * (2 * attn_weight_params(c) + mlp)


def attn_score_flops(c: dict, pairs: float) -> float:
    """Scores and weighted values for ``pairs`` query-key pairs, all layers."""
    _, h, _, hd, layers, _ = _dims(c)
    return layers * 4 * h * hd * pairs


def prefill_flops(c: dict, s: int) -> float:
    """Batch-1 prefill of an ``s``-token prompt: every position through
    the layers, causal attention, and the head at the last position."""
    return (s * token_linear_flops(c) + attn_score_flops(c, s * (s + 1) / 2)
            + 2 * head_params(c))


def decode_flops(c: dict, kv_lens) -> float:
    """One decode step over the active slots; ``kv_lens`` holds each
    active slot's cache length after the step's token is written."""
    n = len(kv_lens)
    return (n * (token_linear_flops(c) + 2 * head_params(c))
            + attn_score_flops(c, sum(kv_lens)))


def experts_touched(c: dict, tokens: int) -> float:
    """Expected distinct experts per layer that ``tokens`` tokens route
    to, under uniform routing (E(1 - (1 - k/E)^n))."""
    e, k = _moe(c)
    return e * (1.0 - (1.0 - k / e) ** tokens)


def decode_bytes(c: dict, kv_lens) -> float:
    """Least bytes one decode step moves: every weight it needs once
    (the experts its tokens route to), the valid KV rows of each active
    slot, and the one new KV row per slot it writes."""
    d, h, kv, hd, layers, v = _dims(c)
    b = DTYPE_BYTES[c["torch_dtype"]]
    n = len(kv_lens)
    e, _ = _moe(c)
    experts = experts_touched(c, n) if e else None
    weights = (layers * (attn_weight_params(c) + mlp_weight_params(c, experts))
               + head_params(c))
    kv_row = layers * 2 * kv * hd
    return b * (weights + kv_row * (sum(kv_lens) + n))
