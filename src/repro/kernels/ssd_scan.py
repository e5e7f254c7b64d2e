"""Mamba2 SSD (state-space duality) chunked scan as a Pallas TPU kernel.

TPU-native adaptation: the chunk dimension is the innermost grid axis (TPU
grids are sequential), so the inter-chunk SSM state (P x N, f32) lives in VMEM
scratch and is carried chunk-to-chunk — the HBM<->VMEM traffic per chunk is
exactly the chunk's inputs/outputs, and the quadratic intra-chunk work runs on
the MXU as (Q x N)(N x Q) and (Q x Q)(Q x P) matmuls. The in-chunk cumulative
sum of dt·a is an exact f32 masked reduction over a (Q x Q) broadcast — no
sequential scan, and no reduced-precision matmul pass in the exponent.

Layouts: x (B, H, S, P); dt (B, H, S); A (H,); Bm/Cm (B, S, N).
Chunk length Q must divide S. Output y (B, H, S, P) and final state
(B, H, P, N).

Every block obeys the TPU tiling rule (the two minor block dims divisible
by (8, 128) or equal to the array's): dt is viewed as (B, H, S/Q, 1, Q) so
a chunk's step sizes arrive as one (1, Q) row whatever Q is, and the per-
head scalars (A; dt in the decode step) live whole in SMEM. The decode
step views x and y as (B, H, P, 1) columns and B/C as (B, 1, N) rows, so
the rank-1 update is a plain column-times-row broadcast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array, scalar reads


def _ssd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref,  # inputs (a_ref: (H,) in SMEM)
    y_ref, fs_ref,                        # outputs
    state_ref,                            # scratch: (P, N) f32 carried state
    *,
    chunk: int,
    num_chunks: int,
):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, P)
    dt_row = dt_ref[0, 0, 0].astype(jnp.float32)   # (1, Q)
    bm = b_ref[0].astype(jnp.float32)              # (Q, N)
    cm = c_ref[0].astype(jnp.float32)              # (Q, N)

    q = chunk
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # dt as a column too, read off the diagonal of its row broadcast
    dt_col = jnp.sum(jnp.where(ii == jj, dt_row, 0.0), axis=1, keepdims=True)
    a = a_ref[h]
    da_row, da_col = dt_row * a, dt_col * a        # (1, Q), (Q, 1)
    # inclusive cumulative sums cs_i = sum_{k<=i} dA_k, as exact f32
    # masked reductions in both orientations (no transpose needed)
    cs_col = jnp.sum(jnp.where(jj <= ii, da_row, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(ii <= jj, da_col, 0.0), axis=0, keepdims=True)
    total = jnp.sum(da_row, axis=1, keepdims=True)  # (1, 1) = cs_{Q-1}

    # decay matrix L[i,j] = exp(cs_i - cs_j) for i>=j else 0
    L = jnp.where(ii >= jj, jnp.exp(cs_col - cs_row), 0.0)

    xdt = x * dt_col                               # (Q, P)
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * L                                          # (Q, Q)
    y_diag = jax.lax.dot_general(
        scores, xdt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (Q, P)

    # inter-chunk: contribution of the entering state
    state = state_ref[...]                         # (P, N)
    c_state = jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (Q, P)
    y_off = c_state * jnp.exp(cs_col)

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: state' = state * exp(cs[-1]) + sum_j e^{cs[-1]-cs_j} dt_j x_j B_j^T
    xw = xdt * jnp.exp(total - cs_col)             # (Q, P)
    upd = jax.lax.dot_general(
        xw, bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                              # (P, N)
    state_ref[...] = state * jnp.exp(total) + upd

    @pl.when(ci == num_chunks - 1)
    def _emit_state():
        fs_ref[0, 0] = state_ref[...]


def ssd_scan_bhsp(
    x: jax.Array,   # (B, H, S, P)
    dt: jax.Array,  # (B, H, S)
    A: jax.Array,   # (H,)
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    b, h, s, p = x.shape
    n = Bm.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    grid = (b, h, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    y, fs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda b_, h_, ci: (b_, h_, ci, 0, 0)),
            _SMEM,
            pl.BlockSpec((1, chunk, n), lambda b_, h_, ci: (b_, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, ci: (b_, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, ci: (b_, h_, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, ci: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt.reshape(b, h, nc, 1, chunk), A.astype(jnp.float32), Bm, Cm)
    return y, fs


def _ssd_decode_kernel(s_ref, x_ref, dt_ref, a_ref, b_ref, c_ref,
                       y_ref, ns_ref):
    bi = pl.program_id(0)
    h = pl.program_id(1)
    state = s_ref[0, 0]                              # (P, N) f32
    x = x_ref[0, 0].astype(jnp.float32)              # (P, 1)
    dt = dt_ref[bi, h]                               # f32 scalar (SMEM)
    a = a_ref[h]                                     # f32 scalar (SMEM)
    bm = b_ref[0].astype(jnp.float32)                # (1, N)
    cm = c_ref[0].astype(jnp.float32)                # (1, N)
    new = state * jnp.exp(dt * a) + (dt * x) * bm
    ns_ref[0, 0] = new
    y_ref[0, 0] = jnp.sum(new * cm, axis=1, keepdims=True).astype(y_ref.dtype)


def ssd_decode_step_bh(
    state: jax.Array,  # (B, H, P, N) f32
    x_t: jax.Array,    # (B, H, P)
    dt_t: jax.Array,   # (B, H)
    A: jax.Array,      # (H,)
    B_t: jax.Array,    # (B, N)
    C_t: jax.Array,    # (B, N)
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Single-token SSD recurrence, one (batch, head) cell per grid step.

    The decode-path analogue of :func:`ssd_scan_bhsp`: the rank-1 state
    update h' = e^{dt·a} h + (dt·x) B^T and the readout y = h' C run fused
    in VMEM. Returns (y (B,H,P) in x's dtype, new_state (B,H,P,N) f32).
    """
    b, h, p = x_t.shape
    n = B_t.shape[-1]
    y, ns = pl.pallas_call(
        _ssd_decode_kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, p, n), lambda b_, h_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, p, 1), lambda b_, h_: (b_, h_, 0, 0)),
            _SMEM,
            _SMEM,
            pl.BlockSpec((1, 1, n), lambda b_, h_: (b_, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda b_, h_: (b_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, p, 1), lambda b_, h_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, p, 1), x_t.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(state, x_t[..., None], dt_t.astype(jnp.float32), A.astype(jnp.float32),
      B_t[:, None], C_t[:, None])
    return y[..., 0], ns
