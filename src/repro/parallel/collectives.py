"""Explicit collectives via shard_map: tensor-parallel serving + gradients.

GSPMD inserts collectives implicitly everywhere else in this repo; this
module is where we drop to ``jax.shard_map`` for collectives the compiler
cannot (or should not) synthesize:

* **Tensor-parallel serving reductions.** The serving executor
  (``serving/executor.py``) runs the fused decode/prefill steps under
  ``shard_map`` on a ``("model",)`` mesh with attention heads, MLP ff and
  (untied) unembed columns sharded Megatron-style. Model code marks the
  reduction points with :func:`psum_tp` (row-parallel output projections:
  attention ``wo``, MLP ``w_down``) and :func:`all_gather_logits`
  (column-parallel unembed -> full-vocab logits for sampling). Both are
  IDENTITY outside a :func:`tensor_parallel` context, so the same model
  code runs unsharded (training, lockstep engine, 1-device serving)
  without change.
* **Error-feedback int8-compressed gradient all-reduce**
  (1-bit-Adam-family trick, here at 8 bits).

    g_compressed = quantize_int8(g + error_carry)
    all-reduce(g_compressed)            # 4x fewer wire bytes than fp32
    error_carry = (g + error_carry) - dequant(g_compressed)

The error carry makes the quantization *unbiased over time* — the residual
of step t is re-injected at t+1, so long-run drift vanishes (standard error
feedback / EF-SGD result). Used for the cross-pod (DCN-ish) reduction where
wire bytes hurt most; the carry lives in the train state.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# tensor-parallel context (serving executor)
# ---------------------------------------------------------------------------

_TP = threading.local()


@contextmanager
def tensor_parallel(axis: str | None, *, vocab_sharded: bool = False):
    """Declare that the enclosed model code is being traced inside a
    ``shard_map`` over mesh axis ``axis`` with Megatron-style weight
    sharding (heads/kv_heads/ff -> ``axis``; unembed columns too when
    ``vocab_sharded``). :func:`psum_tp` / :func:`all_gather_logits` become
    real collectives inside this context and stay identity outside it.

    ``axis=None`` is an explicit no-op (1-device mesh / unsharded runs
    share the code path). Thread-local, so concurrent serving workers with
    different meshes don't interfere.
    """
    prev = (getattr(_TP, "axis", None), getattr(_TP, "vocab", False))
    _TP.axis, _TP.vocab = axis, vocab_sharded and axis is not None
    try:
        yield
    finally:
        _TP.axis, _TP.vocab = prev


def tp_axis() -> str | None:
    """Mesh axis of the ambient :func:`tensor_parallel` context (or None)."""
    return getattr(_TP, "axis", None)


def psum_tp(x: jax.Array) -> jax.Array:
    """Sum partial products over the tensor-parallel axis.

    Model code calls this exactly where a row-parallel matmul leaves a
    partial sum on each shard (attention output projection, MLP down
    projection, MoE expert down projection). Identity outside a
    :func:`tensor_parallel` context.
    """
    ax = tp_axis()
    return jax.lax.psum(x, ax) if ax is not None else x


def pmean_tp(x: jax.Array) -> jax.Array:
    """Mean over the tensor-parallel axis.

    Normalization layers whose reduction axis is sharded (the Mamba gated
    RMSNorm runs over the ff-sharded ``d_inner`` dim) need the *global*
    mean of squares; since every shard holds an equal-size slice, the
    global mean is exactly the mean of the shard-local means. Identity
    outside a :func:`tensor_parallel` context.
    """
    ax = tp_axis()
    return jax.lax.pmean(x, ax) if ax is not None else x


def all_gather_logits(x: jax.Array) -> jax.Array:
    """Reassemble full-vocab logits from a column-parallel unembed.

    Sampling (greedy argmax / top-k / top-p) needs the whole vocab row, so
    the shard-local logits slice is gathered (tiled) along the last axis.
    Identity outside a :func:`tensor_parallel` context and when the vocab
    dim is replicated (tied embeddings keep the embedding table — and thus
    the logits — replicated; gathering replicated logits would wrongly
    tile them).
    """
    ax = tp_axis()
    if ax is None or not getattr(_TP, "vocab", False):
        return x
    return jax.lax.all_gather(x, ax, axis=x.ndim - 1, tiled=True)


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """int8-compressed psum: quantize locally, sum int32, dequant.

    Wire bytes: 1 byte/elt for the payload (+1 scalar) vs 4 for fp32.
    Scales are max-combined so dequantization is conservative (no overflow:
    the int32 accumulator holds up to 2^23 shards of int8 exactly).
    """
    q, scale = quantize_int8(x)
    scale_max = jax.lax.pmax(scale, axis_name)
    # requantize against the shared scale so the integer sum is coherent
    q2 = jnp.clip(jnp.round(x / scale_max), -127, 127).astype(jnp.int32)
    total = jax.lax.psum(q2, axis_name)
    return total.astype(jnp.float32) * scale_max


def make_compressed_grad_allreduce(mesh: Mesh, axis: str = "data"):
    """Returns ef_allreduce(grads, error_carry) -> (mean_grads, new_carry).

    grads are expected replicated along ``axis``'s orthogonal dims per the
    usual DP layout; each leaf is reduced over ``axis`` with int8 payloads
    and an error-feedback carry of the same shape.
    """
    n = dict(zip(mesh.axis_names, mesh.shape.values()))[axis]

    def _leaf(g, carry, n_shards):
        corrected = g.astype(jnp.float32) + carry
        summed = compressed_psum(corrected, axis)
        mean = summed / n_shards
        # what this shard actually contributed after quantization
        q, scale = quantize_int8(corrected)
        sent = dequantize_int8(q, scale)
        new_carry = corrected - sent
        return mean.astype(g.dtype), new_carry

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=(P(), P(axis)),
    )
    def _reduce_flat(gs, carries):
        # gs: this shard's stacked flat grads (1, N); carries same
        g = gs[0]
        c = carries[0]
        mean, new_c = _leaf(g, c, float(n))
        return mean, new_c[None]

    def ef_allreduce(grad_shards: jax.Array, error_carry: jax.Array):
        """grad_shards: (n_shards, N) — per-DP-shard flat gradients."""
        return _reduce_flat(grad_shards, error_carry)

    return ef_allreduce


def flatten_grads(grads) -> tuple[jax.Array, any]:
    leaves, treedef = jax.tree.flatten(grads)
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
    return flat, (treedef, [(l.shape, l.dtype) for l in leaves])


def unflatten_grads(flat: jax.Array, meta) -> any:
    treedef, shapes = meta
    out, off = [], 0
    for shape, dtype in shapes:
        n = 1
        for d in shape:
            n *= d
        out.append(flat[off:off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(treedef, out)
