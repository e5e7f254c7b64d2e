"""Pure-jnp oracles for the Pallas kernels.

These are the correctness ground truth (tests sweep shapes/dtypes and
``assert_allclose`` kernel vs. ref) AND the XLA fallback implementation the
models use on non-TPU backends.

* ``flash_attention_ref``         — naive full-matrix attention (small inputs
  only).
* ``flash_attention_chunked``     — online-softmax over KV chunks (bounded
  memory; what the models lower on XLA; numerically equal to naive).
* ``paged_attention_ref``         — single-token decode over a block-table
  page pool; oracle for ``paged_attention.py`` and the XLA decode path of
  the continuous-batching engine. Idle slots (length 0) yield zeros.
* ``paged_prefill_attention_ref`` — chunked prefill: a chunk of C queries of
  one sequence over its paged prefix + itself (causal). The C=1 case
  degenerates to ``paged_attention_ref``; oracle for the Pallas
  chunk-prefill kernel (``paged_attention.paged_prefill_attention_ckgd``)
  and the XLA/CPU serving path.
* ``paged_mixed_attention_ref``   — fused mixed step: R independent rows,
  each a (block-table row, last attended position) pair — decode rows and
  one prefill chunk's rows share a single dispatch. ``last_pos < 0`` marks
  a dead/padded row (exact zeros). Oracle for the Pallas mixed kernel
  (``paged_attention.paged_mixed_attention_rkgd``) and the XLA fused-step
  serving path; subsumes both refs above.
* ``ssd_sequential``              — Mamba2 SSD as the literal per-token
  recurrence.
* ``ssd_chunked``                 — the SSD block-decomposition (Dao & Gu
  2024), matches ``ssd_sequential``; what the models lower on XLA.

The paged oracles read the logical ``(P, page, KVH, D)`` view of one
layer's K and V; the serving pool stores them lane-dense and stacked over
layers, and ``unpack_kv`` is the one way from that layout to this view.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """(B, S, KVH, D) -> (B, S, H, D) by group repetition."""
    b, s, kvh, d = k.shape
    if kvh == num_q_heads:
        return k
    rep = num_q_heads // kvh
    return jnp.repeat(k, rep, axis=2)


def flash_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    """Naive reference. q: (B, Sq, H, D); k/v: (B, Skv, KVH, D)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        # queries are the LAST sq positions of the skv keys (supports Sq<Skv)
        qpos = jnp.arange(sq)[:, None] + (skv - sq)
        kpos = jnp.arange(skv)[None, :]
        logits = jnp.where(kpos <= qpos, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def flash_attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    chunk_kv: int = 512,
) -> jax.Array:
    """Online-softmax attention, scanning KV chunks. Memory O(Sq * chunk)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    kvh = k.shape[2]
    group = h // kvh
    chunk_kv = min(chunk_kv, skv)
    assert skv % chunk_kv == 0, (skv, chunk_kv)
    nkv = skv // chunk_kv

    # grouped views; keep kv heads un-repeated (GQA native)
    qg = q.reshape(b, sq, kvh, group, d).astype(jnp.float32) * scale
    kc = k.reshape(b, nkv, chunk_kv, kvh, d)
    vc = v.reshape(b, nkv, chunk_kv, kvh, d)
    kc = jnp.moveaxis(kc, 1, 0)  # (nkv, b, ckv, kvh, d)
    vc = jnp.moveaxis(vc, 1, 0)

    qpos = jnp.arange(sq) + (skv - sq)  # absolute position of each query

    # flash-attention memory semantics require NOT saving per-chunk logits
    # as scan residuals — checkpoint the body so backward recomputes them
    @jax.checkpoint
    def body(carry, inp):
        acc, m, l = carry
        idx, kblk, vblk = inp
        logits = jnp.einsum(
            "bqhgd,bkhd->bqhgk", qg, kblk.astype(jnp.float32)
        )  # (b, sq, kvh, g, ckv)
        if causal:
            kpos = idx * chunk_kv + jnp.arange(chunk_kv)
            mask = kpos[None, :] <= qpos[:, None]  # (sq, ckv)
            logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqhgk,bkhd->bqhgd", p, vblk.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (acc_new, m_new, l_new), ()

    acc0 = jnp.zeros((b, sq, kvh, group, d), jnp.float32)
    m0 = jnp.full((b, sq, kvh, group), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kvh, group), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0), (jnp.arange(nkv), kc, vc)
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# int8 KV page quantization (tiered cache)
# ---------------------------------------------------------------------------


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization over the head_dim (last) axis.

    x (..., D) -> (q int8 (..., D), scale f32 (...,)): one absmax scale per
    (position, head), so dequantization is a row broadcast the paged kernels
    fuse into their K/V loads. The worst-case per-element error is scale/2
    (round-to-nearest over a +/-127 grid) — the quantize->dequant round-trip
    property in ``tests/test_kernel_fuzz.py`` asserts exactly that bound.
    """
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)  # all-zero rows quantize to zeros
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def dequantize_pages(pages: jax.Array, scales: jax.Array) -> jax.Array:
    """int8 pages (..., D) * per-row scales (...,) -> f32 pages.

    The XLA fallback for the quantized paged kernels: dequantize the pool,
    then run the unchanged fp32 oracle — so the fp32 refs stay the single
    ground truth and the Pallas fused-dequant variants are compared against
    ``dequantize_pages`` + the existing oracle in the fuzz harness.
    """
    return pages.astype(jnp.float32) * scales[..., None]


# ---------------------------------------------------------------------------
# the page pool's lane-dense layout
# ---------------------------------------------------------------------------


def pack_kv(k: jax.Array, v: jax.Array) -> jax.Array:
    """Logical K and V (..., KVH, D) -> the page pool's lane-dense rows
    (..., KVH*2*D): kv head h's K then its V fill lanes [2Dh, 2D(h+1)), so
    a page's minor dims fill whole (16, 128) bf16 tiles and a kv-head shard
    is one contiguous lane range."""
    return jnp.concatenate([k, v], axis=-1).reshape(*k.shape[:-2], -1)


def pack_kv_scales(k_scale: jax.Array, v_scale: jax.Array) -> jax.Array:
    """int8 scales (..., KVH) each -> (..., KVH*2): head h's K scale, then
    its V scale, in the same head order as :func:`pack_kv`."""
    return jnp.stack([k_scale, v_scale], axis=-1).reshape(
        *k_scale.shape[:-1], -1)


def unpack_kv(kv: jax.Array, head_dim: int,
              kv_scale: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """The inverse of :func:`pack_kv`: lane-dense rows (..., KVH*2*D) ->
    logical (K, V), each (..., KVH, D). With ``kv_scale`` (int8 pools) both
    come back dequantized to f32. The one view through which the oracles,
    the XLA lowering and the tests read the pool."""
    x = kv.reshape(*kv.shape[:-1], -1, 2, head_dim)
    k, v = x[..., 0, :], x[..., 1, :]
    if kv_scale is not None:
        s = kv_scale.reshape(*kv_scale.shape[:-1], -1, 2)
        k, v = dequantize_pages(k, s[..., 0]), dequantize_pages(v, s[..., 1])
    return k, v


# ---------------------------------------------------------------------------
# paged attention (single-token decode over a block-table KV pool)
# ---------------------------------------------------------------------------


def paged_attention_ref(
    q: jax.Array,             # (B, H, D) one query token per sequence
    k_pages: jax.Array,       # (P, page, KVH, D) shared page pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, MP) int32 physical page per logical page
    lengths: jax.Array,       # (B,) int32 valid positions per sequence
    *,
    scale: float | None = None,
) -> jax.Array:
    """Gather-based oracle for the paged decode kernel.

    Each sequence reads its K/V through the block table; positions >= length
    are masked. A sequence with length 0 (an idle slot) returns zeros — the
    same convention as the Pallas kernel, so idle decode slots never produce
    NaNs. Returns (B, H, D) in q.dtype.
    """
    b, h, d = q.shape
    _, page, kvh, _ = k_pages.shape
    mp = block_tables.shape[1]
    group = h // kvh
    scale = scale if scale is not None else d ** -0.5

    # (B, MP, page, KVH, D) -> (B, MP*page, KVH, D): logical contiguous view
    keys = k_pages[block_tables].reshape(b, mp * page, kvh, d)
    vals = v_pages[block_tables].reshape(b, mp * page, kvh, d)

    qg = q.reshape(b, kvh, group, d).astype(jnp.float32) * scale
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg, keys.astype(jnp.float32)
    )  # (B, KVH, G, MP*page)
    valid = jnp.arange(mp * page)[None, :] < lengths[:, None]  # (B, S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    # explicit normalization (not jax.nn.softmax) so an all-masked row gives 0
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m) * valid[:, None, None, :]
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p / jnp.maximum(l, 1e-30),
                     vals.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_prefill_attention_ref(
    q: jax.Array,            # (C, H, D) one chunk of queries for ONE sequence
    k_pages: jax.Array,      # (P, page, KVH, D) shared page pool
    v_pages: jax.Array,
    block_table: jax.Array,  # (MP,) int32 the sequence's block-table row
    start: jax.Array,        # scalar int32: positions already cached
    valid: jax.Array,        # scalar int32: real (non-padded) chunk tokens
    *,
    scale: float | None = None,
) -> jax.Array:
    """Chunked-prefill oracle: chunk queries over the paged prefix + chunk.

    Query i (absolute position start+i) attends to every cached position
    <= start+i, read through the block table — the chunk's own K/V must
    already be scattered into the pages (``attention`` does the scatter
    before calling this). Padded queries (i >= valid) return zeros. The
    masked-softmax convention matches :func:`paged_attention_ref`, of which
    this is the multi-query generalization (that kernel is the C=1 case).
    Returns (C, H, D) in q.dtype.
    """
    c, h, d = q.shape
    _, page, kvh, _ = k_pages.shape
    mp = block_table.shape[0]
    group = h // kvh
    scale = scale if scale is not None else d ** -0.5

    keys = k_pages[block_table].reshape(mp * page, kvh, d)
    vals = v_pages[block_table].reshape(mp * page, kvh, d)

    qg = q.reshape(c, kvh, group, d).astype(jnp.float32) * scale
    scores = jnp.einsum(
        "ckgd,skd->ckgs", qg, keys.astype(jnp.float32)
    )  # (C, KVH, G, MP*page)
    kpos = jnp.arange(mp * page)[None, :]
    qpos = start + jnp.arange(c)[:, None]
    ok = (kpos <= qpos) & (jnp.arange(c)[:, None] < valid)  # (C, S)
    scores = jnp.where(ok[:, None, None, :], scores, NEG_INF)
    # explicit normalization (not jax.nn.softmax) so an all-masked row gives 0
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m) * ok[:, None, None, :]
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("ckgs,skd->ckgd", p / jnp.maximum(l, 1e-30),
                     vals.astype(jnp.float32))
    return out.reshape(c, h, d).astype(q.dtype)


def paged_mixed_attention_ref(
    q: jax.Array,             # (R, H, D) one query row per batch row
    k_pages: jax.Array,       # (P, page, KVH, D) shared page pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # (R, MP) int32 block-table row per query row
    last_pos: jax.Array,      # (R,) int32 last attendable position, -1 = dead
    *,
    scale: float | None = None,
) -> jax.Array:
    """Mixed-batch oracle: every row attends positions ``<= last_pos[r]``.

    One predicate covers the whole fused step: a decode row at length L
    (its new token already scattered at position L) uses ``last_pos = L``;
    chunk query i of a prefill at cursor ``start`` uses
    ``last_pos = start + i``; padded rows (idle decode slots, chunk rows
    past ``valid``) use ``last_pos = -1`` and return exact zeros — the same
    no-NaN convention as :func:`paged_attention_ref`, of which this is the
    per-row generalization (decode is ``last_pos = lengths - 1``; a chunk
    is C consecutive rows sharing one block-table row). Returns (R, H, D)
    in q.dtype.
    """
    r, h, d = q.shape
    _, page, kvh, _ = k_pages.shape
    mp = block_tables.shape[1]
    group = h // kvh
    scale = scale if scale is not None else d ** -0.5

    # (R, MP, page, KVH, D) -> (R, MP*page, KVH, D): logical contiguous view
    keys = k_pages[block_tables].reshape(r, mp * page, kvh, d)
    vals = v_pages[block_tables].reshape(r, mp * page, kvh, d)

    qg = q.reshape(r, kvh, group, d).astype(jnp.float32) * scale
    scores = jnp.einsum(
        "rkgd,rskd->rkgs", qg, keys.astype(jnp.float32)
    )  # (R, KVH, G, MP*page)
    ok = jnp.arange(mp * page)[None, :] <= last_pos[:, None]  # (R, S)
    scores = jnp.where(ok[:, None, None, :], scores, NEG_INF)
    # explicit normalization (not jax.nn.softmax) so an all-masked row gives 0
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m) * ok[:, None, None, :]
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("rkgs,rskd->rkgd", p / jnp.maximum(l, 1e-30),
                     vals.astype(jnp.float32))
    return out.reshape(r, h, d).astype(q.dtype)


def paged_verify_attention_ref(
    q: jax.Array,            # (C, H, D) bundle queries: t_last + k drafts
    k_pages: jax.Array,      # (P, page, KVH, D) shared page pool
    v_pages: jax.Array,
    block_table: jax.Array,  # (MP,) int32 the sequence's block-table row
    start: jax.Array,        # scalar int32: cached length L before the bundle
    valid: jax.Array,        # scalar int32: 1 + number of drafted tokens
    *,
    scale: float | None = None,
) -> jax.Array:
    """Speculative-verify oracle: score a k-token draft bundle in one pass.

    Row i is the query for absolute position ``start + i`` (row 0 is the
    last committed token, rows 1..k the drafts) and must attend exactly the
    positions a sequential i-step decode loop would see: the cached prefix
    plus the bundle rows ``<= i`` (the bundle's own K/V already scattered
    at ``start .. start+valid-1``). That predicate is precisely the mixed
    kernel's chunk half, so this oracle delegates to
    :func:`paged_mixed_attention_ref` with broadcast tables and positions
    ``start + i`` (dead past ``valid``) — pinning down, as executable
    documentation, that verify == chunk attention == an unrolled decode
    loop. ``tests/test_kernel_fuzz.py`` asserts all three agree to 1e-3
    for k in 1..8, including COW-forked and preempted-resumed tables.
    Returns (C, H, D) in q.dtype; padded rows are exact zeros.
    """
    c = q.shape[0]
    idx = jnp.arange(c)
    last_pos = jnp.where(idx < valid, start + idx, -1).astype(jnp.int32)
    tables = jnp.broadcast_to(block_table, (c,) + block_table.shape)
    return paged_mixed_attention_ref(
        q, k_pages, v_pages, tables, last_pos, scale=scale
    )


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------


def ssd_sequential(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)       softplus-activated step sizes
    A: jax.Array,      # (H,)            negative decay rates
    Bm: jax.Array,     # (B, S, N)       input projection (G=1 group)
    Cm: jax.Array,     # (B, S, N)       output projection
    init_state: jax.Array | None = None,  # (B, H, P, N)
) -> tuple[jax.Array, jax.Array]:
    """Literal recurrence: h_t = exp(dt_t A) h_{t-1} + dt_t * x_t B_t^T ;  y_t = h_t C_t."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = Bm.astype(jnp.float32)
    Cf = Cm.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, bt, ct = inp  # (b,h,p), (b,h), (b,n), (b,n)
        decay = jnp.exp(dtt * Af[None, :])  # (b,h)
        upd = jnp.einsum("bh,bhp,bn->bhpn", dtt, xt, bt)
        state = state * decay[..., None, None] + upd
        y = jnp.einsum("bhpn,bn->bhp", state, ct)
        return state, y

    init = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )
    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(Bf, 1, 0),
        jnp.moveaxis(Cf, 1, 0),
    )
    state, ys = jax.lax.scan(step, init, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)  # (B,S,H,P)
    return y, state


def _segsum(dA: jax.Array) -> jax.Array:
    """Stable 'segment sum': L[..., i, j] = sum_{k=j+1..i} dA[..., k] for i>=j else -inf.

    dA: (..., Q). Returns (..., Q, Q) lower-triangular log-decay matrix.
    """
    q = dA.shape[-1]
    cs = jnp.cumsum(dA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # cs_i - cs_j = sum_{j+1..i}
    iota = jnp.arange(q)
    mask = iota[:, None] >= iota[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    Bm: jax.Array,
    Cm: jax.Array,
    init_state: jax.Array | None = None,
    *,
    chunk: int = 256,
) -> tuple[jax.Array, jax.Array]:
    """Block decomposition of the SSD recurrence (matches ssd_sequential).

    Splits S into chunks of length Q; within-chunk term is a masked
    attention-like matmul, cross-chunk term is a scan over chunk states.
    """
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    xf = x.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, h)
    Bf = Bm.astype(jnp.float32).reshape(b, nc, chunk, n)
    Cf = Cm.astype(jnp.float32).reshape(b, nc, chunk, n)
    Af = A.astype(jnp.float32)

    dA = dtf * Af[None, None, None, :]            # (b,nc,q,h)
    dA = jnp.moveaxis(dA, -1, -2)                  # (b,nc,h,q)
    L = jnp.exp(_segsum(dA))                       # (b,nc,h,q,q)
    dA_cs = jnp.cumsum(dA, axis=-1)                # (b,nc,h,q)
    dA_total = dA_cs[..., -1]                      # (b,nc,h)

    # ---- intra-chunk (diagonal blocks) ----
    scores = jnp.einsum("bcin,bcjn->bcij", Cf, Bf)         # (b,nc,q,q)
    scores = scores[:, :, None] * L                         # (b,nc,h,q,q)
    xdt = xf * dtf[..., None]                               # (b,nc,q,h,p)
    y_diag = jnp.einsum("bchij,bcjhp->bcihp", scores, xdt)

    # ---- chunk states: contribution of each chunk to the carried state ----
    decay_to_end = jnp.exp(dA_cs[..., -1:] - dA_cs)         # (b,nc,h,q)
    states = jnp.einsum(
        "bchq,bcqn,bcqhp->bchpn", decay_to_end, Bf, xdt
    )                                                        # (b,nc,h,p,n)

    # ---- scan chunk states ----
    def step(carry, inp):
        st, dtot = inp  # (b,h,p,n), (b,h)
        new = carry * jnp.exp(dtot)[..., None, None] + st
        return new, carry  # emit the state ENTERING this chunk

    init = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((b, h, p, n), jnp.float32)
    )
    final_state, entering = jax.lax.scan(
        step, init, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(dA_total, 1, 0))
    )
    entering = jnp.moveaxis(entering, 0, 1)                 # (b,nc,h,p,n)

    # ---- inter-chunk output: y_off[i] = (C_i . state_in) * exp(dA_cs[i]) ----
    decay_from_start = jnp.exp(dA_cs)                        # (b,nc,h,q)
    y_off = jnp.einsum(
        "bcqn,bchpn,bchq->bcqhp", Cf, entering, decay_from_start
    )

    y = (y_diag + y_off).reshape(b, s, h, p).astype(x.dtype)
    return y, final_state


def ssd_decode_step(
    state: jax.Array,  # (B, H, P, N) f32
    x_t: jax.Array,    # (B, H, P)
    dt_t: jax.Array,   # (B, H)
    A: jax.Array,      # (H,)
    B_t: jax.Array,    # (B, N)
    C_t: jax.Array,    # (B, N)
) -> tuple[jax.Array, jax.Array]:
    """One-token SSD recurrence for serving."""
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(dtf * A.astype(jnp.float32)[None, :])
    upd = jnp.einsum(
        "bh,bhp,bn->bhpn", dtf, x_t.astype(jnp.float32), B_t.astype(jnp.float32)
    )
    state = state * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, C_t.astype(jnp.float32))
    return y.astype(x_t.dtype), state
