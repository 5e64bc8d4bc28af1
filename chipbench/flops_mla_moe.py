"""Operations and bytes of a latent-attention, mixture-of-experts model
(DeepSeek-V2's block) from its shapes alone, at one chip's share of the
experts.

Counted by the algorithm, as ``flops.py`` counts a dense model: a decode row
attends over the keys it has, whatever cache capacity the program reads; a
prompt computes the output head at its last position only.  Decode attends
in the latent space (the up-projections absorbed into the query and the
output), prefill over keys and values decompressed per head.  The routed
experts are not counted per token: the work of a step is
``expert_flops(c) x`` its assignments to the experts held here, which the
program counts.  ``c`` is a configuration's ``model`` block.
"""

from __future__ import annotations

from chipbench.flops import ITEMSIZE


# -- latent attention ---------------------------------------------------------

def mla_decode_bytes(c: dict, kv_len: int) -> int:
    """Latent rows (the normed latent and the shared rope key, in the
    serving type) one decode query reads once over ``kv_len`` keys, all
    layers."""
    row = (c["kv_lora_rank"] + c["rope_head_dim"]) * ITEMSIZE[c["dtype"]]
    return row * kv_len * c["n_layers"]


def mla_decode_flops(c: dict, kv_len: int) -> int:
    """Absorbed decode of one query over ``kv_len`` keys, all layers: per
    head and key the latent and rope scores (2 x (rank + rope dims)) and the
    latent weighted sum (2 x rank)."""
    per_key = c["n_heads"] * (4 * c["kv_lora_rank"] + 2 * c["rope_head_dim"])
    return per_key * kv_len * c["n_layers"]


def mla_prefill_flops(c: dict, kv_len: int) -> int:
    """One prompt position over ``kv_len`` keys decompressed per head, all
    layers: scores over the no-rope and rope dims, then the values."""
    h = c["n_heads"]
    per_key = 2 * h * (c["nope_head_dim"] + c["rope_head_dim"]
                       + c["v_head_dim"])
    return per_key * kv_len * c["n_layers"]


def mla_params(c: dict) -> int:
    """Weights one token multiplies through in one attention layer: the
    query, the latent down-projection, both up-projections (into the keys
    and values in prefill, into the query and the output in decode), the
    output projection."""
    d, h = c["d_model"], c["n_heads"]
    dc, dr = c["kv_lora_rank"], c["rope_head_dim"]
    dn, dv = c["nope_head_dim"], c["v_head_dim"]
    return d * h * (dn + dr) + d * (dc + dr) + dc * h * (dn + dv) + h * dv * d


# -- experts --------------------------------------------------------------------

def expert_flops(c: dict) -> int:
    """One token through one routed expert (SwiGLU: three matrices)."""
    return 6 * c["d_model"] * c["d_expert"]


def expert_bytes(c: dict) -> int:
    """One routed expert's weights, in the serving type."""
    return 3 * c["d_model"] * c["d_expert"] * ITEMSIZE[c["dtype"]]


def dense_layer_params(c: dict, layer: int) -> int:
    """Weights every token multiplies through in a layer beside attention
    and the routed experts: the dense MLP of a leading dense layer, else the
    router and the shared experts."""
    d = c["d_model"]
    if layer == 0 and c["first_layer_dense"]:
        return 3 * d * c["d_ff"]
    return d * c["n_routed_experts"] + 3 * d * c["d_expert"] * c["n_shared_experts"]


def token_flops(c: dict) -> int:
    """One token through every layer's weights but the routed experts', and
    through the output head."""
    per_layer = sum(2 * (mla_params(c) + dense_layer_params(c, i))
                    for i in range(c["n_layers"]))
    return per_layer + 2 * c["d_model"] * c["vocab_size"]


def prefill_flops(c: dict, prompt_len: int) -> int:
    """A prompt's work but the routed experts': every position through the
    layers, the head at the last one."""
    head = 2 * c["d_model"] * c["vocab_size"]
    keys = prompt_len * (prompt_len + 1) // 2      # position p attends to p + 1
    return (prompt_len * (token_flops(c) - head)
            + mla_prefill_flops(c, keys) + head)


def decode_flops(c: dict, kv_len: int) -> int:
    """A decode row's work but the routed experts'."""
    return token_flops(c) + mla_decode_flops(c, kv_len)
