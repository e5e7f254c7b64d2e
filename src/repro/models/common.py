"""Shared model building blocks: declarative params, norms, RoPE.

Params are plain pytrees (nested dicts of arrays). Each model defines a
``param_specs(cfg)`` tree of :class:`ParamSpec`; from it we derive

* ``init_params``    — real arrays (deterministic per-path RNG folding),
* ``abstract_params``— ShapeDtypeStructs (dry-run: no allocation),
* ``param_axes``     — logical-axis tuples (sharding).
"""

from __future__ import annotations

import dataclasses
import zlib
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.collectives import psum_tp


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"  # normal | zeros | ones | embed | ssm_a_log | ssm_dt_bias
    scale: float | None = None
    dtype: str | None = None  # None -> model default

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _leaf_dtype(spec: ParamSpec, default: str):
    return jnp.dtype(spec.dtype or default)


def init_params(specs: Any, key: jax.Array, default_dtype: str) -> Any:
    """Materialize a ParamSpec tree into real arrays.

    RNG is folded per tree-path so adding a parameter never reshuffles the
    others (checkpoint/elastic stability).
    """
    leaves = jax.tree.leaves_with_path(specs, is_leaf=_is_spec)

    def one(path, spec: ParamSpec):
        dt = _leaf_dtype(spec, default_dtype)
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dt)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dt)
        # deterministic across processes (hash() is PYTHONHASHSEED-random)
        seed = zlib.crc32(jax.tree_util.keystr(path).encode()) % (2**31)
        k = jax.random.fold_in(key, seed)
        if spec.init == "ssm_a_log":
            # Mamba2's decay range: A ~ U[1, 16], stored as log A
            a = jax.random.uniform(k, spec.shape, jnp.float32, 1.0, 16.0)
            return jnp.log(a).astype(dt)
        if spec.init == "ssm_dt_bias":
            # Mamba2's step range: dt log-uniform in [1e-3, 1e-1], stored as
            # softplus^-1(dt); a unit-scale bias instead makes steps ~0.7
            # and the stack too sensitive to bf16 rounding to compare paths
            lo, hi = np.log(1e-3), np.log(1e-1)
            step = jnp.exp(lo + (hi - lo) * jax.random.uniform(k, spec.shape))
            return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        if spec.init == "embed":
            scale = spec.scale if spec.scale is not None else 1.0
        else:
            fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
            scale = spec.scale if spec.scale is not None else 1.0 / np.sqrt(fan_in)
        return (jax.random.normal(k, spec.shape, jnp.float32) * scale).astype(dt)

    vals = [one(p, s) for p, s in leaves]
    return jax.tree.unflatten(jax.tree.structure(specs, is_leaf=_is_spec), vals)


def abstract_params(specs: Any, default_dtype: str) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, _leaf_dtype(s, default_dtype)),
        specs,
        is_leaf=_is_spec,
    )


def param_axes(specs: Any) -> Any:
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=_is_spec)


def param_bytes(specs: Any, default_dtype: str) -> int:
    return sum(
        int(np.prod(s.shape)) * _leaf_dtype(s, default_dtype).itemsize
        for s in jax.tree.leaves(specs, is_leaf=_is_spec)
    )


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x, weight, bias, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def rope_table(positions: jax.Array, head_dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for given integer positions. Shapes (..., head_dim//2)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2) (broadcast over heads)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # (S, half)
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:  # (B, S, half)
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dt)


def swiglu(x, w_gate, w_up, w_down, constrain=None):
    """SwiGLU MLP. Weights: (D,F), (D,F), (F,D).

    Under a serving :func:`repro.parallel.tensor_parallel` context the ff
    dim is sharded (gate/up column-parallel, down row-parallel) and the
    down projection's partial sum is reduced here; outside it ``psum_tp``
    is identity.
    """
    g = jnp.einsum("bsd,df->bsf", x, w_gate)
    u = jnp.einsum("bsd,df->bsf", x, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    if constrain is not None:
        h = constrain(h, "batch", "seq", "ff")
    return psum_tp(jnp.einsum("bsf,fd->bsd", h, w_down))


# ---------------------------------------------------------------------------
# sampling (the fused sample step shared by every serving engine)
# ---------------------------------------------------------------------------


def sample_tokens(
    logits: jax.Array,   # (B, Vp) — padded vocab ok, sliced to `vocab`
    temps: jax.Array,    # (B,) f32; <= 0 means greedy (filters ignored)
    top_ks: jax.Array,   # (B,) int32; 0 disables top-k
    top_ps: jax.Array,   # (B,) f32; 1.0 disables top-p
    seeds: jax.Array,    # (B,) int32 per-request RNG seed
    idx: jax.Array,      # (B,) int32 token index within each request
    vocab: int,
) -> jax.Array:
    """Per-row temperature / top-k / top-p sampling, one fused dispatch.

    RNG is keyed off ``(seed, token_index)`` per row — NEVER off an
    engine-global step counter — so a request reproduces the same stream no
    matter which slot it lands in, how it is batched, or whether it was
    preempted and regenerated. Sampling uses the Gumbel-max trick over the
    filtered logits; greedy rows (``temps <= 0``) take the plain argmax.

    The top-k/top-p filters cost one vocab sort per row per step. That is
    fine for the CPU/reference path and small reduced vocabs; a
    Pallas-fused filter is future kernel work, not an API concern.
    """
    lg = logits[..., :vocab].astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def one(row, temp, k, p, seed, i):
        v = row.shape[-1]
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        desc = jnp.sort(row)[::-1]
        # top-k: keep logits >= the k-th largest (k=0 -> keep all)
        kth = desc[jnp.clip(jnp.where(k > 0, k, v) - 1, 0, v - 1)]
        row = jnp.where(row < kth, -jnp.inf, row)
        t = jnp.maximum(temp, 1e-6)
        # top-p (nucleus) over the top-k-filtered distribution: keep the
        # smallest prefix of descending probabilities whose mass reaches p
        probs = jax.nn.softmax(row / t)
        p_desc = jnp.sort(probs)[::-1]
        csum = jnp.cumsum(p_desc)
        cutoff = jnp.where(p >= 1.0, 0.0, p_desc[jnp.argmax(csum >= p)])
        row = jnp.where(probs < cutoff, -jnp.inf, row)
        g = jax.random.gumbel(key, row.shape, jnp.float32)
        return jnp.argmax(row / t + g).astype(jnp.int32)

    sampled = jax.vmap(one)(lg, temps, top_ks, top_ps, seeds, idx)
    return jnp.where(temps > 0.0, sampled, greedy)
