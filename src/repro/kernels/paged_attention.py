"""Paged attention (decode, chunked prefill, fused mixed) as Pallas TPU kernels.

Three kernels over the same page-pool layout (`kv_cache.PagedKVCache`):

* ``paged_attention_bkgd`` — DECODE: one query token per sequence attends
  over K/V stored in the shared page pool; pages are gathered *inside the
  grid* via a scalar-prefetched block table, so sequences of wildly
  different lengths share one decode batch with zero re-padding and no
  dense gather in HBM. Oracle: ``ref.paged_attention_ref`` — identical
  masking/normalization conventions, idle (length-0) slots return exact
  zeros, never NaN.
* ``paged_prefill_attention_ckgd`` — CHUNKED PREFILL: C queries of ONE
  sequence (absolute positions ``start+i``) attend causally over the
  sequence's paged prefix *plus the chunk itself* (whose K/V the caller
  already scattered into the pages). Oracle:
  ``ref.paged_prefill_attention_ref``; padded queries (``i >= valid``)
  return exact zeros. The C=1, start=length-1 case degenerates to decode.
* ``paged_mixed_attention_rkgd`` — FUSED MIXED STEP: R rows, each carrying
  its OWN block-table row and a single scalar ``last_pos`` (the last
  attendable absolute position; ``-1`` = dead row -> exact zeros). Decode
  rows (``last_pos = length``, the just-scattered token) and one prefill
  chunk's C rows (``last_pos = start + i`` for live rows) ride in one
  dispatch, so a full-occupancy engine step is one kernel launch. Oracle:
  ``ref.paged_mixed_attention_ref``; subsumes both kernels above.

All three walk pages with one body (:func:`_page_walk`). The grid's last
axis is the logical page, innermost — TPU grid steps are sequential, so
the online-softmax state (acc, m, l) lives in VMEM scratch and carries
across the pages of one query block, reusing the scratch pattern from
``flash_attention.py``. The BlockSpec index_map reads the block table
(scalar prefetch) to DMA the right physical page; pages past what a query
block may read map to the reserved null page 0 and are skipped via
``pl.when``.

The pool is stacked over layers and lane-dense (``kv_cache.PagedKVCache``):
``kv_pages`` is ``(L, P, page, KVH*2*D)``, kv head h's K then its V in
lanes ``[2Dh, 2D(h+1))`` (``ref.pack_kv``). Every kernel takes the WHOLE
stacked pool plus the layer as a scalar prefetch, and its page BlockSpec
maps a grid cell to ``(layer, bt[.., p], 0, 0)``: no per-layer slice of the
pool ever exists outside VMEM, so the model's layer loop can carry the
pool and write each layer's new rows in place. A page block ``(1, 1,
page, KVH*2*D)`` has the pool's own two minor dims, which the TPU
compiler's tiling rule (minor block dims divisible by (8, 128) or equal
to the array's) admits for any head count, and for D = 64 each head's
K|V pair is one 128-lane group: the block fills (16, 128) bf16 tiles
with no padding. The kernel walks the heads with a static loop; head h's
K and V are static lane slices of the VMEM block (no HBM traffic). GQA is
native: q arrives grouped ``(.., KVH, G, D)`` and head h's G grouped
queries attend to kv head h's page rows.

* decode / mixed grid: (row, logical-page); q block ``(1, KVH, G, D)``.
* prefill grid: (logical-page,); the chunk's grouped queries (flattened to
  C*G rows per kv head) stay resident in VMEM across the page walk.
  Causality is a per-row mask (``kpos <= start + row//G``), so a chunk
  straddling a page boundary, a partial last page, a COW-forked table or
  history length 0 all fall out of the one mask. Pages wholly past the
  chunk's last live query (``p*page >= start+valid``) are skipped.

All three kernels optionally take int8 pages with a per-(position, head)
``kv_scale`` pool (shape (L, P, page, KVH*2), f32, head h's K scale then
its V scale, ``ref.pack_kv_scales``): the scales ride the SAME
scalar-prefetched layer and block table as their pages (one ``(1, 1, page,
KVH*2)`` block per cell) and dequantization is fused into the VMEM page
load (``k * scale[:, 2h]``), so no HBM-resident f32 copy ever exists.
Oracle: ``ref.unpack_kv`` (which dequantizes) + the fp32 refs.

Tensor-parallel serving dispatches every kernel PER SHARD: the serving
executor's ``shard_map`` hands each device its contiguous lane range of
the page pool (KVH/tp whole head groups) and the matching grouped-q slice,
with block tables, lengths and the layer replicated. Nothing in the
kernels changes — the page block's lane extent is just the local
``KVH/tp*2*D``, and because pages shard only along the lanes, the
scalar-prefetched block-table values (physical page ids) are identical on
every shard.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # TPU vector lane count; scratch stats padded to it


def _page_walk(q_ref, kv_ref, s_ref, o_ref, acc_ref, m_ref, l_ref, *, p,
               run, mask, scale: float, num_logical_pages: int):
    """Online-softmax step of one page for every local kv head.

    ``q_ref``/``o_ref``: ``(1, KVH, rows, D)`` blocks; ``kv_ref``: ``(1, 1,
    page, KVH*2*D)``, head h's K in lanes ``[2Dh, 2Dh+D)`` and its V in
    ``[2Dh+D, 2D(h+1))``; ``s_ref``: ``(1, 1, page, KVH*2)`` int8 scales or
    None; scratch ``acc (KVH, rows, D)``, ``m``/``l (KVH, rows, LANES)``
    (column 0 used). ``p`` is the logical page, ``run`` whether this page
    holds any position the block may read, and ``mask(shape)`` the (rows,
    page) bool of attendable (query, key) pairs — the same for every head.
    Rows that attend nothing finalize to exact zeros.
    """
    kvh, d = q_ref.shape[1], q_ref.shape[3]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(run)
    def _compute():
        ok = None
        for h in range(kvh):
            q = q_ref[0, h].astype(jnp.float32)        # (rows, D)
            lo = 2 * d * h
            k = kv_ref[0, 0, :, lo:lo + d].astype(jnp.float32)  # (page, D)
            v = kv_ref[0, 0, :, lo + d:lo + 2 * d].astype(jnp.float32)
            if s_ref is not None:
                # int8 pages: dequant fused into the page load — one row
                # scale per (position, head), never materialized in HBM
                k = k * s_ref[0, 0, :, 2 * h:2 * h + 1]
                v = v * s_ref[0, 0, :, 2 * h + 1:2 * h + 2]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                  # (rows, page)
            if ok is None:
                ok = mask(s.shape)
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_ref[h, :, 0:1]                  # (rows, 1)
            l_prev = l_ref[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pexp = jnp.where(ok, jnp.exp(s - m_new), 0.0)  # exact zeros
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(pexp, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                pexp, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[h] = acc_ref[h] * corr + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(p == num_logical_pages - 1)
    def _finalize():
        # max(l, eps): a block that attended nothing finalizes to zeros
        for h in range(kvh):
            l = l_ref[h, :, 0:1]
            o_ref[0, h] = (
                acc_ref[h] / jnp.maximum(l, 1e-30)
            ).astype(o_ref.dtype)


def _split_rest(rest, quant: bool):
    """[s_ref when quant], o_ref, acc_ref, m_ref, l_ref."""
    if quant:
        return rest
    return (None,) + tuple(rest)


def _paged_call(kernel, prefetch, q, kv_pages, kv_scale, layer, *,
                name: str, grid, q_map, page_map, interpret: bool):
    """Shared ``pallas_call`` plumbing of the three kernels: q/out blocks of
    ``(1, KVH, rows, D)``, one page of one layer of the stacked pool (and
    its scales) per grid cell, and the per-head online-softmax scratch.
    The layer is the first scalar prefetch; ``q_map`` and ``page_map``
    take the grid indices and the kernel's own prefetch refs, and
    ``page_map`` returns the physical page. ``name`` names the kernel's
    custom call in the compiled HLO, and so in a profiler trace."""
    _, kvh, rows, d = q.shape
    _, _, page_size, width = kv_pages.shape
    ng = len(grid)

    def pool_spec(width_):
        return pl.BlockSpec(
            (1, 1, page_size, width_),
            lambda *ix: (ix[ng][0], page_map(*ix[:ng], *ix[ng + 1:]), 0, 0),
        )

    q_spec = pl.BlockSpec(
        (1, kvh, rows, d), lambda *ix: q_map(*ix[:ng], *ix[ng + 1:])
    )
    in_specs = [q_spec, pool_spec(width)]
    operands = [q, kv_pages]
    if kv_scale is not None:
        in_specs.append(pool_spec(kv_scale.shape[-1]))
        operands.append(kv_scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(prefetch),
        grid=grid,
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kvh, rows, d), jnp.float32),       # acc
            pltpu.VMEM((kvh, rows, _LANES), jnp.float32),  # m (col 0 used)
            pltpu.VMEM((kvh, rows, _LANES), jnp.float32),  # l (col 0 used)
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=name,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), *prefetch,
      *operands)


def _check(q, kv_pages, kv_scale, scale):
    kvh, d = q.shape[1], q.shape[-1]
    assert kv_pages.ndim == 4 and kv_pages.shape[-1] == kvh * 2 * d, (
        kv_pages.shape, kvh, d)
    assert kv_scale is None or kv_scale.shape == kv_pages.shape[:-1] + (
        kvh * 2,), (kv_scale.shape, kv_pages.shape)
    return scale if scale is not None else d ** -0.5


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _paged_kernel(
    layer_ref,  # (1,) int32 scalar-prefetch: the pool's layer (index maps)
    bt_ref,    # (B, MP) int32 scalar-prefetch: block tables
    len_ref,   # (B,)  int32 scalar-prefetch: valid positions per sequence
    q_ref, kv_ref,  # VMEM blocks
    *rest,     # [s_ref when quant], o_ref, acc_ref, m_ref, l_ref
    scale: float,
    page_size: int,
    num_logical_pages: int,
    quant: bool = False,
):
    b = pl.program_id(0)
    p = pl.program_id(1)
    length = len_ref[b]

    def mask(shape):
        pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return pos < length

    # pages entirely past the valid prefix hold no live positions: skip
    _page_walk(q_ref, kv_ref, *_split_rest(rest, quant),
               p=p, run=p * page_size < length, mask=mask, scale=scale,
               num_logical_pages=num_logical_pages)


def paged_attention_bkgd(
    q: jax.Array,             # (B, KVH, G, D) grouped query, one token per seq
    kv_pages: jax.Array,      # (L, P, page, KVH*2*D) stacked lane-dense pool
    block_tables: jax.Array,  # (B, MP) int32
    lengths: jax.Array,       # (B,) int32
    layer: jax.Array,         # scalar int32: the pool's layer to read
    *,
    kv_scale: jax.Array | None = None,  # (L, P, page, KVH*2) f32 int8 scales
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    scale = _check(q, kv_pages, kv_scale, scale)
    b, mp = block_tables.shape
    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=kv_pages.shape[2],
        num_logical_pages=mp, quant=kv_scale is not None,
    )
    return _paged_call(
        kernel, (block_tables, lengths), q, kv_pages, kv_scale, layer,
        name="paged_decode_attention", grid=(b, mp),
        q_map=lambda b_, p_, bt, ln: (b_, 0, 0, 0),
        # physical page comes from the prefetched block table
        page_map=lambda b_, p_, bt, ln: bt[b_, p_],
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


def _paged_prefill_kernel(
    layer_ref,  # (1,) int32 scalar-prefetch: the pool's layer (index maps)
    bt_ref,    # (MP,) int32 scalar-prefetch: the sequence's block-table row
    meta_ref,  # (2,)  int32 scalar-prefetch: [start, valid]
    q_ref, kv_ref,  # VMEM blocks
    *rest,     # [s_ref when quant], o_ref, acc_ref, m_ref, l_ref
    scale: float,
    page_size: int,
    num_logical_pages: int,
    group: int,
    quant: bool = False,
):
    p = pl.program_id(0)
    start = meta_ref[0]
    valid = meta_ref[1]

    def mask(shape):
        # q rows are the chunk flattened to (C*G, D): row r = chunk position
        # r // G, grouped head r % G — one mask expression covers causality,
        # chunk padding, partial pages and page-straddling chunks at once
        kpos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        ci = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
        return (kpos <= start + ci) & (ci < valid)

    # pages wholly past the chunk's last live query attend nothing: skip.
    # (valid == 0 leaves every row fully masked -> exact zeros, like the ref)
    _page_walk(q_ref, kv_ref, *_split_rest(rest, quant),
               p=p, run=p * page_size < start + valid, mask=mask,
               scale=scale, num_logical_pages=num_logical_pages)


def paged_prefill_attention_ckgd(
    q: jax.Array,            # (C, KVH, G, D) grouped chunk queries, ONE seq
    kv_pages: jax.Array,     # (L, P, page, KVH*2*D) stacked lane-dense pool
    block_table: jax.Array,  # (MP,) int32 the sequence's block-table row
    start: jax.Array,        # scalar int32: positions already cached
    valid: jax.Array,        # scalar int32: real (non-padded) chunk tokens
    layer: jax.Array,        # scalar int32: the pool's layer to read
    *,
    kv_scale: jax.Array | None = None,  # (L, P, page, KVH*2) f32 int8 scales
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill paged attention; mirrors the decode kernel's contract
    (scalar-prefetched layer and block table, per-shard head slice under
    the serving executor's ``shard_map``). Returns (C, KVH, G, D) in
    q.dtype."""
    c, kvh, group, d = q.shape
    scale = _check(q, kv_pages, kv_scale, scale)
    mp = block_table.shape[0]
    # (C, KVH, G, D) -> (1, KVH, C*G, D): all of one kv head's grouped
    # queries become contiguous rows of one matmul operand
    qf = jnp.transpose(q, (1, 0, 2, 3)).reshape(1, kvh, c * group, d)
    meta = jnp.stack([
        jnp.asarray(start, jnp.int32), jnp.asarray(valid, jnp.int32)
    ])
    kernel = functools.partial(
        _paged_prefill_kernel, scale=scale, page_size=kv_pages.shape[2],
        num_logical_pages=mp, group=group, quant=kv_scale is not None,
    )
    out = _paged_call(
        kernel, (block_table, meta), qf, kv_pages, kv_scale, layer,
        name="paged_prefill_attention", grid=(mp,),
        q_map=lambda p_, bt, mt: (0, 0, 0, 0),
        page_map=lambda p_, bt, mt: bt[p_],
        interpret=interpret,
    )
    return jnp.transpose(out.reshape(kvh, c, group, d), (1, 0, 2, 3))


# ---------------------------------------------------------------------------
# fused mixed step (decode rows + one prefill chunk, one dispatch)
# ---------------------------------------------------------------------------


def _paged_mixed_kernel(
    layer_ref,  # (1,) int32 scalar-prefetch: the pool's layer (index maps)
    bt_ref,    # (R, MP) int32 scalar-prefetch: block-table row per query row
    lp_ref,    # (R,)   int32 scalar-prefetch: last attendable position, -1 dead
    q_ref, kv_ref,  # VMEM blocks
    *rest,     # [s_ref when quant], o_ref, acc_ref, m_ref, l_ref
    scale: float,
    page_size: int,
    num_logical_pages: int,
    quant: bool = False,
):
    r = pl.program_id(0)
    p = pl.program_id(1)
    lp = lp_ref[r]

    def mask(shape):
        # the ONE mask of the fused step: decode causality, chunk causality,
        # partial pages and dead rows are all "position <= last_pos"
        pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return pos <= lp

    # pages entirely past the row's last attendable position hold nothing
    # it may read: skip. A dead row (lp < 0) skips every page -> exact zeros.
    _page_walk(q_ref, kv_ref, *_split_rest(rest, quant),
               p=p, run=p * page_size <= lp, mask=mask, scale=scale,
               num_logical_pages=num_logical_pages)


def paged_mixed_attention_rkgd(
    q: jax.Array,             # (R, KVH, G, D) grouped query, one row per row
    kv_pages: jax.Array,      # (L, P, page, KVH*2*D) stacked lane-dense pool
    block_tables: jax.Array,  # (R, MP) int32, one block-table row per row
    last_pos: jax.Array,      # (R,) int32 last attendable position, -1 = dead
    layer: jax.Array,         # scalar int32: the pool's layer to read
    *,
    kv_scale: jax.Array | None = None,  # (L, P, page, KVH*2) f32 int8 scales
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused mixed-step paged attention; the decode kernel's grid with the
    prefill kernel's per-row causal predicate collapsed to one prefetched
    scalar per row. Same shard-local contract as the other two kernels
    (per-shard head slice under the executor's ``shard_map``, tables,
    positions and layer replicated). Returns (R, KVH, G, D) in q.dtype."""
    scale = _check(q, kv_pages, kv_scale, scale)
    r, mp = block_tables.shape
    kernel = functools.partial(
        _paged_mixed_kernel, scale=scale, page_size=kv_pages.shape[2],
        num_logical_pages=mp, quant=kv_scale is not None,
    )
    return _paged_call(
        kernel, (block_tables, last_pos), q, kv_pages, kv_scale, layer,
        name="paged_mixed_attention", grid=(r, mp),
        q_map=lambda r_, p_, bt, lp: (r_, 0, 0, 0),
        # physical page comes from the row's prefetched block table
        page_map=lambda r_, p_, bt, lp: bt[r_, p_],
        interpret=interpret,
    )
