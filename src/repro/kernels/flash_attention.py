"""Flash attention as a Pallas TPU kernel.

TPU-native adaptation (not a CUDA port): the grid walks (batch, q-head,
q-block, kv-block) with the kv-block dimension innermost — TPU grid steps are
sequential, so the online-softmax state (acc, m, l) lives in VMEM scratch and
carries across kv-blocks of the same q-block. GQA is expressed in the
BlockSpec index_map (q-head h reads kv-head h // group), so grouped heads
never materialize repeated K/V in HBM. MXU alignment: block_q x head_dim and
block_kv x head_dim tiles, f32 accumulation.

Layout: q (B, H, Sq, D); k/v (B, KVH, Skv, D). ``ops.flash_attention`` handles
the (B, S, H, D) <-> (B, H, S, D) transposes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # TPU vector lane count; scratch stats padded to it


def _flash_kernel(
    q_ref, k_ref, v_ref,  # VMEM blocks
    o_ref,
    acc_ref, m_ref, l_ref,  # VMEM scratch
    *,
    scale: float,
    causal: bool,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
    q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip kv blocks strictly after the last query of this q block
    first_q = qi * block_q + q_offset
    last_q = first_q + block_q - 1
    first_k = ki * block_kv
    run = jnp.logical_or(jnp.logical_not(causal), first_k <= last_q)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if causal:
            qpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0) + first_q
            kpos = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1) + first_k
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, KVH, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, d = q.shape
    _, kvh, skv, _ = k.shape
    assert h % kvh == 0, (h, kvh)
    group = h // kvh
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0, (sq, block_q, skv, block_kv)
    nq, nkv = sq // block_q, skv // block_kv
    q_offset = skv - sq  # queries are the last sq of skv positions

    grid = (b, h, nq, nkv)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=nkv,
        q_offset=q_offset,
    )
    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),   # acc
        pltpu.VMEM((block_q, _LANES), jnp.float32),  # m (col 0 used)
        pltpu.VMEM((block_q, _LANES), jnp.float32),  # l (col 0 used)
    ]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
