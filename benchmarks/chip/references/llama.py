"""Plain reference of a Llama-architecture decoder (SmolLM), in float32.

Follows the published Llama block (Hugging Face ``LlamaForCausalLM``):
RMSNorm before attention and MLP, rotary embeddings in the rotate-half
layout with ``inv_freq = theta^(-2i/head_dim)``, grouped-query attention
(query head j reads key/value head j // (heads / kv_heads)) with a causal
softmax scaled by 1/sqrt(head_dim), a SwiGLU MLP, a final RMSNorm and the
output projection (the transposed embedding when tied). It imports nothing
of the program: it reads the benchmark's own weight tree by name and runs
one sequence at a time, layer by layer, so only one layer's weights are
ever held in float32.

``quant`` computes the same model in a lower precision, as the control of
the output check: every matmul weight rounded to int8 or fp8 (e4m3) with
one scale per output channel, activations rounded to bfloat16 before each
matmul, float32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from references import common


def dims(conf: dict) -> dict:
    c = dict(conf["config"], **conf.get("as_run", {}))
    heads = c["num_attention_heads"]
    return {
        "layers": c["num_hidden_layers"], "d": c["hidden_size"],
        "heads": heads, "kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim", c["hidden_size"] // heads),
        "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
        "theta": float(c["rope_theta"]), "eps": float(c["rms_norm_eps"]),
        "tied": bool(c["tie_word_embeddings"]),
    }


def _rope(x, pos, theta):
    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dm", "quant", "count"))
def _forward(params, tokens, first, dm, quant, count):
    dm = dict(dm)
    mm = functools.partial(common.matmul, quant=quant)
    eps = dm["eps"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    emb = params["embed"]
    x = common.embed_lookup(emb, tokens, quant)
    causal = jnp.tril(jnp.ones((s, s), bool))
    g = dm["heads"] // dm["kv_heads"]
    scale = dm["head_dim"] ** -0.5

    def layer(x, pl):
        a = pl["attn"]
        h = common.rms_norm(x, pl["ln1"], eps)
        q = mm("sd,dhk->shk", h, a["wq"], (0,))
        k = mm("sd,dhk->shk", h, a["wk"], (0,))
        v = mm("sd,dhk->shk", h, a["wv"], (0,))
        q, k = _rope(q, pos, dm["theta"]), _rope(k, pos, dm["theta"])
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("shk,thk->hst", q, k,
                        precision=jax.lax.Precision.HIGHEST) * scale
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hst,thk->shk", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + mm("shk,hkd->sd", o, a["wo"], (0, 1))
        h = common.rms_norm(x, pl["ln2"], eps)
        m = pl["mlp"]
        gate = mm("sd,df->sf", h, m["w_gate"], (0,))
        up = mm("sd,df->sf", h, m["w_up"], (0,))
        x = x + mm("sf,fd->sd", jax.nn.silu(gate) * up, m["w_down"], (0,))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, first, count)
    x = common.rms_norm(x, params["final_norm"], eps)
    if dm["tied"]:
        logits = mm("sd,vd->sv", x, emb, (1,))
    else:
        logits = mm("sd,dv->sv", x, params["unembed"], (0,))
    return logits[:, :dm["vocab"]]


def logits(params, tokens, first: int, count: int, dm: dict,
           quant: str | None = None):
    """Float32 logits (count, vocab) at positions [first, first + count)
    of one sequence, given all its tokens. The sequence is padded at its
    end (causal: padding changes no earlier row) to a multiple of 512
    and the row count to a multiple of 128, so that few shapes compile."""
    rows = -(-count // 128) * 128
    padded = common.pad_to(tokens, 512, first + rows)
    with jax.default_matmul_precision("highest"):
        out = _forward(params, padded, first, tuple(sorted(dm.items())),
                       quant, rows)
    return out[:count]
