"""Mamba2 (SSD) block: in_proj -> causal depthwise conv -> SSD scan -> gated norm -> out_proj.

Sharding: the inner width d_inner (and its head view H = d_inner / P) is
tensor-parallel over "model"; the SSD state (B, H, P, N) therefore shards on
H. B/C projections (state dim N) are small and replicated. The depthwise conv
is split into separate x / B / C convolutions so each stream keeps a clean
sharding (mathematically identical to the fused conv — it is depthwise).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.common import ParamSpec
from repro.parallel import constrain
from repro.parallel.collectives import pmean_tp, psum_tp


def mamba_param_specs(cfg: ModelConfig, stacked: int | None = None) -> dict:
    d, din, n, h, w = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.conv_width
    pre = (stacked,) if stacked else ()
    pax = ("stack",) if stacked else ()
    return {
        "w_z": ParamSpec(pre + (d, din), pax + ("embed", "ff")),
        "w_x": ParamSpec(pre + (d, din), pax + ("embed", "ff")),
        "w_b": ParamSpec(pre + (d, n), pax + ("embed", None)),
        "w_c": ParamSpec(pre + (d, n), pax + ("embed", None)),
        "w_dt": ParamSpec(pre + (d, h), pax + ("embed", "ssm_heads")),
        "conv_x": ParamSpec(pre + (w, din), pax + (None, "ff"), scale=0.5),
        "conv_b": ParamSpec(pre + (w, n), pax + (None, None), scale=0.5),
        "conv_c": ParamSpec(pre + (w, n), pax + (None, None), scale=0.5),
        "a_log": ParamSpec(pre + (h,), pax + ("ssm_heads",), init="ssm_a_log"),
        "d_skip": ParamSpec(pre + (h,), pax + ("ssm_heads",), init="ones"),
        "dt_bias": ParamSpec(pre + (h,), pax + ("ssm_heads",),
                             init="ssm_dt_bias"),
        "norm": ParamSpec(pre + (din,), pax + ("ff",), init="ones"),
        "w_out": ParamSpec(pre + (din, d), pax + ("ff", "embed")),
    }


def _causal_conv(
    x: jax.Array, w: jax.Array, tail: jax.Array | None = None, valid=None
):
    """Depthwise causal conv. x (B,S,C), w (W,C), tail (B,W-1,C) carry-in.

    Returns (y (B,S,C), new_tail (B,W-1,C)). ``valid`` (scalar, traced ok)
    marks how many leading positions of ``x`` are real tokens: the carried
    tail then ends at position ``valid`` instead of S, so a partially
    filled prefill chunk hands the next chunk the right conv window.
    """
    width = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)  # (B, S+W-1, C)
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(width)
    )
    if width > 1:
        if valid is None:
            new_tail = xp[:, -(width - 1):, :]
        else:
            # tokens occupy xp[:, W-1 : W-1+valid]; the (W-1)-wide window
            # ending at the last valid token starts at xp[:, valid]
            new_tail = jax.lax.dynamic_slice_in_dim(xp, valid, width - 1, axis=1)
    else:
        new_tail = tail
    return y.astype(x.dtype), new_tail


def _gated_norm(p, gated, cfg: ModelConfig):
    """RMS norm over d_inner. d_inner is ff-sharded under tensor parallelism,
    so the mean of squares is averaged across shards (equal-size slices make
    the mean-of-local-means exact); identity reduction when unsharded."""
    dt = gated.dtype
    g32 = gated.astype(jnp.float32)
    var = pmean_tp(jnp.mean(jnp.square(g32), axis=-1, keepdims=True))
    g32 = g32 * jax.lax.rsqrt(var + cfg.norm_eps)
    return (g32 * p["norm"].astype(jnp.float32)).astype(dt)


def _pre_ssd(p, x, cfg: ModelConfig, conv_tails=None, valid=None):
    """Shared projection + conv path. Returns SSD inputs and conv tails."""
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    xs = jnp.einsum("bsd,de->bse", x, p["w_x"])
    bm = jnp.einsum("bsd,dn->bsn", x, p["w_b"])
    cm = jnp.einsum("bsd,dn->bsn", x, p["w_c"])
    dt = jnp.einsum("bsd,dh->bsh", x, p["w_dt"])
    xs = constrain(xs, "batch", "seq", "ff")
    tails_in = conv_tails or {"x": None, "b": None, "c": None}
    xs, tx = _causal_conv(xs, p["conv_x"], tails_in["x"], valid)
    bm, tb = _causal_conv(bm, p["conv_b"], tails_in["b"], valid)
    cm, tc = _causal_conv(cm, p["conv_c"], tails_in["c"], valid)
    xs = jax.nn.silu(xs.astype(jnp.float32)).astype(x.dtype)
    bm = jax.nn.silu(bm.astype(jnp.float32)).astype(x.dtype)
    cm = jax.nn.silu(cm.astype(jnp.float32)).astype(x.dtype)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return z, xs, bm, cm, dt, {"x": tx, "b": tb, "c": tc}


def _post_ssd(p, y, xs_heads, z, cfg: ModelConfig):
    """D-skip, gated RMS norm, out projection. y/xs_heads (B,S,H,P)."""
    b, s, h, pdim = y.shape
    d_skip = p["d_skip"].astype(jnp.float32)
    y = y.astype(jnp.float32) + d_skip[None, None, :, None] * xs_heads.astype(jnp.float32)
    y = y.reshape(b, s, h * pdim)
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    gated = _gated_norm(p, gated.astype(z.dtype), cfg)
    gated = constrain(gated, "batch", "seq", "ff")
    # w_out is row-parallel (d_inner sharded): each shard holds a partial sum
    return psum_tp(jnp.einsum("bse,ed->bsd", gated, p["w_out"]))


def mamba_block(
    p, x, cfg: ModelConfig, *, ssd_impl: str = "xla_chunked", return_cache: bool = False
):
    """Full-sequence Mamba2 block. x (B,S,D) -> y (B,S,D) [, cache]."""
    b, s, d = x.shape
    # head count from the runtime width: under shard_map the block sees the
    # LOCAL d_inner shard, so cfg.ssm_heads would over-count by tp
    pn = cfg.ssm_head_dim
    z, xs, bm, cm, dt, tails = _pre_ssd(p, x, cfg)
    xs_h = xs.reshape(b, s, xs.shape[-1] // pn, pn)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y, state = ops.ssd_scan(xs_h, dt, a, bm, cm, chunk=cfg.ssm_chunk, impl=ssd_impl)
    out = _post_ssd(p, y, xs_h, z, cfg)
    if return_cache:
        cache = {"ssm": state, "conv_x": tails["x"], "conv_b": tails["b"], "conv_c": tails["c"]}
        return out, cache
    return out


def mamba_decode(p, x, cache, cfg: ModelConfig, *, ssd_impl: str = "xla_chunked"):
    """One-token Mamba2 step. x (B,1,D); cache {ssm, conv_x, conv_b, conv_c}."""
    b = x.shape[0]
    pn = cfg.ssm_head_dim  # local head count derived below (shard_map-safe)
    tails = {"x": cache["conv_x"], "b": cache["conv_b"], "c": cache["conv_c"]}
    z, xs, bm, cm, dt, tails = _pre_ssd(p, x, cfg, conv_tails=tails)
    xs_h = xs.reshape(b, 1, xs.shape[-1] // pn, pn)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y_t, state = ops.ssd_decode_step(
        cache["ssm"], xs_h[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], impl=ssd_impl
    )
    out = _post_ssd(p, y_t[:, None], xs_h, z, cfg)
    new_cache = {
        "ssm": state,
        "conv_x": tails["x"],
        "conv_b": tails["b"],
        "conv_c": tails["c"],
    }
    return out, new_cache


def mamba_prefill_chunk(
    p, x, cache, cfg: ModelConfig, *, valid, ssd_impl: str = "xla_chunked"
):
    """Chunked-prefill Mamba2 block: continue from a carried cache.

    x (B,C,D) is one fixed-size prompt chunk, of which only the first
    ``valid`` positions (scalar, traced ok) are real tokens. The SSD scan
    starts from ``cache["ssm"]`` and the conv streams from the carried
    tails; padded positions are neutralized by forcing their dt to zero —
    exp(0·a) = 1 decay and 0·x update make them exact identities on the
    recurrence — so the returned cache is the state *after the last valid
    token*, ready for the next chunk or the first decode step.
    """
    b, c, _ = x.shape
    pn = cfg.ssm_head_dim  # local head count derived below (shard_map-safe)
    tails = {"x": cache["conv_x"], "b": cache["conv_b"], "c": cache["conv_c"]}
    z, xs, bm, cm, dt, tails = _pre_ssd(p, x, cfg, conv_tails=tails, valid=valid)
    mask = (jnp.arange(c) < valid).astype(dt.dtype)
    dt = dt * mask[None, :, None]
    xs_h = xs.reshape(b, c, xs.shape[-1] // pn, pn)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    y, state = ops.ssd_scan(
        xs_h, dt, a, bm, cm,
        chunk=cfg.ssm_chunk, impl=ssd_impl, init_state=cache["ssm"],
    )
    out = _post_ssd(p, y, xs_h, z, cfg)
    new_cache = {
        "ssm": state,
        "conv_x": tails["x"],
        "conv_b": tails["b"],
        "conv_c": tails["c"],
    }
    return out, new_cache


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype, abstract: bool = False):
    hn, pn, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    w = cfg.conv_width
    shapes = {
        "ssm": ((batch, hn, pn, n), jnp.float32),
        "conv_x": ((batch, w - 1, cfg.d_inner), dtype),
        "conv_b": ((batch, w - 1, n), dtype),
        "conv_c": ((batch, w - 1, n), dtype),
    }
    if abstract:
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    return {k: jnp.zeros(s, d) for k, (s, d) in shapes.items()}


MAMBA_CACHE_AXES = {
    "ssm": ("cache_batch", "ssm_heads", None, None),
    "conv_x": ("cache_batch", None, "ff"),
    "conv_b": ("cache_batch", None, None),
    "conv_c": ("cache_batch", None, None),
}
