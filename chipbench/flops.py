"""Operations the model's work needs, from its shapes alone.

Counted by the algorithm, not by what the program executes: a decode row
attends to the keys it has (at most a window), whatever cache capacity the
program reads; a prompt computes the output head at its last position only,
as serving needs.  ``c`` is a configuration's ``model`` block.
"""

from __future__ import annotations


def _attn_proj_params(c: dict) -> int:
    d, h, kv, hd = c["d_model"], c["n_heads"], c["n_kv_heads"], c["head_dim"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def _mamba_params(c: dict) -> int:
    if not c.get("hybrid"):
        return 0
    d, h, n = c["d_model"], c["n_heads"], c["ssm_state"]
    e = c["ssm_expand"] * d
    # in-projection, depthwise conv, B and C, dt, out-projection
    return d * 2 * e + c["conv_width"] * e + 2 * e * h * n + e * h + e * d


def _mamba_scan_flops(c: dict) -> int:
    """Per token and layer: decay, outer-product update and readout of the
    (heads, head_dim, state) recurrence."""
    if not c.get("hybrid"):
        return 0
    e = c["ssm_expand"] * c["d_model"]
    return 6 * e * c["ssm_state"]


def layer_params(c: dict) -> int:
    """Weights one token multiplies through in one block."""
    return _attn_proj_params(c) + _mamba_params(c) + 3 * c["d_model"] * c["d_ff"]


def _keys(c: dict, kv_len: int, layer: int) -> int:
    window = c.get("sliding_window") or 0
    if window and layer not in tuple(c.get("global_layers", ())):
        return min(kv_len, window)
    return kv_len


def attention_flops(c: dict, kv_len: int) -> int:
    """Scores and weighted sum of one query over ``kv_len`` keys, all
    layers."""
    per_key = 4 * c["n_heads"] * c["head_dim"]
    return sum(per_key * _keys(c, kv_len, i) for i in range(c["n_layers"]))


#: bytes per element of the serving types a configuration may state
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def attention_bytes(c: dict, kv_len: int) -> int:
    """Keys and values one query reads once over ``kv_len`` keys, all
    layers, in the configuration's serving type."""
    per_key = 2 * c["n_kv_heads"] * c["head_dim"] * ITEMSIZE[c["dtype"]]
    return sum(per_key * _keys(c, kv_len, i) for i in range(c["n_layers"]))


def head_flops(c: dict) -> int:
    return 2 * c["d_model"] * c["vocab_size"]


def token_flops(c: dict, kv_len: int) -> int:
    """One token through every block, attending to ``kv_len`` keys (itself
    included), without the output head."""
    return (c["n_layers"] * (2 * layer_params(c) + _mamba_scan_flops(c))
            + attention_flops(c, kv_len))


def prefill_flops(c: dict, prompt_len: int) -> int:
    return (sum(token_flops(c, p + 1) for p in range(prompt_len))
            + head_flops(c))


def decode_flops(c: dict, kv_len: int) -> int:
    return token_flops(c, kv_len) + head_flops(c)
