"""Plain reference of Mamba2 (SSD), in float32.

Follows the published Mamba2 block (Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060; ``mamba_ssm`` ``Mamba2`` with ngroups 1): RMSNorm, the
input projection to z, x, B, C and dt, a causal depthwise convolution of
width ``d_conv`` over x, B and C followed by SiLU, ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``, the selective state space recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t,

then ``y * silu(z)`` through the gated RMSNorm and the output projection,
added to a float32 residual. The recurrence is computed in the chunked
form of the paper's minimal SSD listing (exact, chunk length 256). The
conv bias the published layer has is absent from the program and taken as
zero here. It imports nothing of the program: it reads the benchmark's own
weight tree by name, one sequence at a time, layer by layer.

``quant`` is the control, as in ``references/llama.py``: matmul weights
rounded to int8 or fp8 per output channel, bfloat16 activations into the
matmuls; the recurrence stays float32, as the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from references import common

# the Mamba2 layer's published defaults (ssm_cfg names only the layer)
LAYER = {"d_state": 128, "headdim": 64, "expand": 2, "d_conv": 4,
         "chunk_size": 256}


def dims(conf: dict) -> dict:
    c = dict(conf["config"], **conf.get("as_run", {}))
    d = c["d_model"]
    din = LAYER["expand"] * d
    return {
        "layers": c["n_layer"], "d": d, "d_inner": din,
        "heads": din // LAYER["headdim"], "head_dim": LAYER["headdim"],
        "state": LAYER["d_state"], "conv": LAYER["d_conv"],
        "chunk": LAYER["chunk_size"], "vocab": c["vocab_size"],
        "eps": 1e-5, "tied": bool(c["tie_embeddings"]),
    }


def _segsum(x):
    """exp-able segment sums: out[..., i, j] = sum x[..., j+1 .. i]
    for j <= i, -inf above the diagonal."""
    t = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def ssd(x, a_dt, b, c, chunk):
    """Minimal chunked SSD from a zero state. x (S, H, P) already scaled
    by dt, a_dt (S, H), b/c (S, N) shared by all heads. Returns y (S,H,P)."""
    s, h, p = x.shape
    nc = s // chunk
    x = x.reshape(nc, chunk, h, p)
    b = b.reshape(nc, chunk, -1)
    c = c.reshape(nc, chunk, -1)
    a = a_dt.reshape(nc, chunk, h).transpose(2, 0, 1)  # (H, nc, l)
    a_cs = jnp.cumsum(a, axis=-1)
    hi = jax.lax.Precision.HIGHEST
    # within chunks
    decay = jnp.exp(_segsum(a))  # (H, nc, l, s)
    cb = jnp.einsum("cln,csn->cls", c, b, precision=hi)
    y = jnp.einsum("hcls,cshp->clhp", decay * cb[None], x, precision=hi)
    # each chunk's final state, from a zero start
    to_end = jnp.exp(a_cs[..., -1:] - a_cs)  # (H, nc, l)
    states = jnp.einsum("cln,hcl,clhp->chpn", b, to_end, x, precision=hi)
    # carried across chunks
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states])
    last = jnp.pad(a_cs[..., -1], ((0, 0), (1, 0)))  # (H, nc+1)
    carry = jnp.exp(_segsum(last))  # (H, nc+1, nc+1)
    states = jnp.einsum("hzc,chpn->zhpn", carry, states, precision=hi)[:-1]
    # carried state read out at each position of the chunk
    y = y + jnp.einsum("cln,chpn,hcl->clhp", c, states, jnp.exp(a_cs),
                       precision=hi)
    return y.reshape(s, h, p)


def _conv(x, w):
    """Causal depthwise conv: y_t = sum_i w[i] x_{t-W+1+i}. x (S,C), w (W,C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(xp[i:i + x.shape[0]] * w[i] for i in range(width))


@functools.partial(jax.jit, static_argnames=("dm", "quant", "count"))
def _forward(params, tokens, first, dm, quant, count):
    dm = dict(dm)
    mm = functools.partial(common.matmul, quant=quant)
    eps = dm["eps"]
    s = tokens.shape[0]
    h, p = dm["heads"], dm["head_dim"]
    x = common.embed_lookup(params["embed"], tokens, quant)

    def layer(x, pl):
        m = pl["mamba"]
        u = common.rms_norm(x, pl["ln"], eps)
        z = mm("sd,de->se", u, m["w_z"], (0,))
        xs = jax.nn.silu(_conv(mm("sd,de->se", u, m["w_x"], (0,)), m["conv_x"]))
        bm = jax.nn.silu(_conv(mm("sd,dn->sn", u, m["w_b"], (0,)), m["conv_b"]))
        cm = jax.nn.silu(_conv(mm("sd,dn->sn", u, m["w_c"], (0,)), m["conv_c"]))
        dt = jax.nn.softplus(mm("sd,dh->sh", u, m["w_dt"], (0,))
                             + m["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(m["a_log"].astype(jnp.float32))
        xh = xs.reshape(s, h, p)
        y = ssd(xh * dt[..., None], dt * a, bm, cm, dm["chunk"])
        y = y + m["d_skip"].astype(jnp.float32)[:, None] * xh
        y = y.reshape(s, h * p) * jax.nn.silu(z)
        y = common.rms_norm(y, m["norm"], eps)
        return x + mm("se,ed->sd", y, m["w_out"], (0,)), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = jax.lax.dynamic_slice_in_dim(x, first, count)
    x = common.rms_norm(x, params["final_norm"], eps)
    if dm["tied"]:
        out = mm("sd,vd->sv", x, params["embed"], (1,))
    else:
        out = mm("sd,dv->sv", x, params["unembed"], (0,))
    return out[:, :dm["vocab"]]


def logits(params, tokens, first: int, count: int, dm: dict,
           quant: str | None = None):
    """Float32 logits (count, vocab) at positions [first, first + count)
    of one sequence, given all its tokens. The sequence is padded at its
    end (causal: padding changes no earlier row) to a multiple of 1024
    and the row count to a multiple of 128, so that few shapes compile."""
    rows = -(-count // 128) * 128
    padded = common.pad_to(tokens, 1024, first + rows)
    with jax.default_matmul_precision("highest"):
        out = _forward(params, padded, first, tuple(sorted(dm.items())),
                       quant, rows)
    return out[:count]
