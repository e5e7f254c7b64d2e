"""Arithmetic the plain references share: float32 matmuls at the highest
precision, or the control's rounded weights and bfloat16 activations."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# largest magnitude each control format holds, for a per-channel scale
QMAX = {"int8": 127.0, "fp8": 448.0}


def quantize(w, in_axes: tuple[int, ...], quant: str):
    """``w`` rounded to ``quant`` with one scale per output channel (the
    scale spans the contracted ``in_axes``), returned dequantized."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / QMAX[quant], 1.0)
    if quant == "int8":
        q = jnp.clip(jnp.round(w / scale), -127, 127)
    elif quant == "fp8":
        q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    else:
        raise ValueError(f"unknown control precision {quant!r}")
    return q * scale


def matmul(spec: str, x, w, in_axes: tuple[int, ...], quant: str | None):
    """einsum ``spec`` of activations ``x`` and weight ``w``: float32 at
    the highest precision, or with ``quant`` the control's rounding."""
    if quant is None:
        return jnp.einsum(spec, x.astype(jnp.float32), w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
    wq = quantize(w, in_axes, quant).astype(jnp.bfloat16)
    return jnp.einsum(spec, x.astype(jnp.bfloat16), wq,
                      preferred_element_type=jnp.float32)


def embed_lookup(table, tokens, quant: str | None):
    """Rows of the embedding table (rounded per row for the control)."""
    rows = jnp.take(table, tokens, axis=0)
    if quant is None:
        return rows.astype(jnp.float32)
    q = quantize(table, (1,), quant)
    return jnp.take(q, tokens, axis=0).astype(jnp.bfloat16).astype(jnp.float32)


def rms_norm(x, w, eps: float):
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def pad_to(tokens, multiple: int, least: int = 0):
    """Tokens as an int32 device array padded with zeros to a multiple of
    ``multiple`` that is at least ``least`` long."""
    t = np.asarray(tokens, np.int32)
    n = -(-max(len(t), least) // multiple) * multiple
    return jnp.asarray(np.pad(t, (0, n - len(t))))
