"""Plain reference of olmo-1b (arXiv:2402.00838) as ``olmo1b.json`` states it.

Pre-norm decoder: non-parametric layer norm, multi-head attention with
rotary positions, SwiGLU MLP, no biases, output head tied to the
embedding.  Float32 at the highest matmul precision over one whole
sequence: no cache, no batching, no kernels.

Weights are drawn from the seed by the recipe the configuration states:
every matrix Normal(0, 1/fan_in) in float32, stored in the serving type,
with the key of each drawn in the order below (``init``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import refcore as rc


def init(key, c: dict) -> dict:
    d, h, kv, hd, f = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                       c["head_dim"], c["d_ff"])
    dt = jnp.dtype(c["dtype"])
    ks = jax.random.split(key, 8)

    def layer(lk):
        bk = jax.random.split(lk, 10)
        ak = jax.random.split(bk[1], 8)
        mk = jax.random.split(bk[8], 3)
        return {
            "wq": rc.dense_init(ak[0], (d, h, hd), d, dt),
            "wk": rc.dense_init(ak[1], (d, kv, hd), d, dt),
            "wv": rc.dense_init(ak[2], (d, kv, hd), d, dt),
            "wo": rc.dense_init(ak[3], (h, hd, d), h * hd, dt),
            "wi": rc.dense_init(mk[0], (d, f), d, dt),
            "wg": rc.dense_init(mk[1], (d, f), d, dt),
            "wo_mlp": rc.dense_init(mk[2], (f, d), f, dt),
        }

    layers = jax.vmap(layer)(jax.random.split(ks[3], c["n_layers"]))
    return {"embed": rc.dense_init(ks[0], (c["vocab_size"], d), d, dt),
            "layers": layers}


def forward(params, tokens, c: dict, mm: rc.Matmul):
    """tokens: (S,) int32 -> next-token logits (S, vocab) float32."""
    h, hd = c["n_heads"], c["head_dim"]
    x = params["embed"][tokens].astype(rc.F32)
    s = x.shape[0]

    def layer(x, p):
        a = rc.layer_norm(x)
        q = rc.rope(mm(a, p["wq"]), c["rope_theta"])
        k = rc.rope(mm(a, p["wk"]), c["rope_theta"])
        v = mm(a, p["wv"])
        o = rc.causal_attention(q, k, v, s)
        x = x + mm(o.reshape(s, h * hd), p["wo"].reshape(h * hd, -1))
        m = rc.layer_norm(x)
        return x + rc.swiglu(mm, m, p["wi"], p["wg"], p["wo_mlp"]), None

    x, _ = lax.scan(layer, x, params["layers"])
    return mm(rc.layer_norm(x), params["embed"].T)
