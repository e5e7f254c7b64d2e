"""Attention blocks: GQA + RoPE (+ optional qk-norm), train/prefill/decode paths.

Sharding: q heads -> "model" (when divisible), kv heads -> "model" (usually
replicated since kv_heads < 16), decode KV cache seq -> "model"
(flash-decoding-style sequence parallelism; see DESIGN.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels.ref import pack_kv, pack_kv_scales, quantize_kv
from repro.models import common
from repro.models.common import ParamSpec, apply_rope, rms_norm, rope_table
from repro.parallel import constrain
from repro.parallel.collectives import psum_tp

NEG_INF = -1e30


def attn_param_specs(cfg: ModelConfig, stacked: int | None = None) -> dict:
    """QKV/O projections (+ qk-norm scales). ``stacked``: leading scan dim.

    Uses the *effective* (possibly padded) head counts; padded o-proj rows
    are zero-init so padding is output-identical at init.
    """
    d, h, kvh, hd = cfg.d_model, cfg.eff_heads, cfg.eff_kv_heads, cfg.head_dim
    pre = (stacked,) if stacked else ()
    pax = ("stack",) if stacked else ()
    wo_init = "zeros" if cfg.num_heads_padded else "normal"
    # fan-in is d for q/k/v and h*hd for o: the shape rule (second-to-last
    # axis) would read h or hd here and blow up attention logits at init
    specs = {
        "wq": ParamSpec(pre + (d, h, hd), pax + ("embed", "heads", "head_dim"),
                        scale=d ** -0.5),
        "wk": ParamSpec(pre + (d, kvh, hd), pax + ("embed", "kv_heads", "head_dim"),
                        scale=d ** -0.5),
        "wv": ParamSpec(pre + (d, kvh, hd), pax + ("embed", "kv_heads", "head_dim"),
                        scale=d ** -0.5),
        "wo": ParamSpec(pre + (h, hd, d), pax + ("heads", "head_dim", "embed"),
                        init=wo_init, scale=(h * hd) ** -0.5),
    }
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec(pre + (hd,), pax + (None,), init="ones")
        specs["k_norm"] = ParamSpec(pre + (hd,), pax + (None,), init="ones")
    return specs


def _project_qkv(p, x, cfg: ModelConfig, positions: jax.Array | None, rope: bool):
    """x (B,S,D) -> q (B,S,H,Dh), k/v (B,S,KVH,Dh), rope-rotated."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        assert positions is not None
        cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def self_attention(
    p: dict,
    x: jax.Array,           # (B, S, D)
    cfg: ModelConfig,
    *,
    causal: bool = True,
    rope: bool = True,
    positions: jax.Array | None = None,  # (S,) int32
    attn_impl: str = "xla_chunked",
) -> jax.Array:
    """Full-sequence self-attention (train / prefill)."""
    if positions is None:
        positions = jnp.arange(x.shape[1])
    q, k, v = _project_qkv(p, x, cfg, positions, rope)
    out = ops.flash_attention(q, k, v, causal=causal, impl=attn_impl)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    # row-parallel wo: partial sum per TP shard, reduced here (identity
    # outside a tensor_parallel context)
    return psum_tp(jnp.einsum("bshk,hkd->bsd", out, p["wo"]))


def self_attention_with_cache_write(
    p, x, cfg: ModelConfig, *, positions=None, attn_impl="xla_chunked",
    rope: bool = True,
):
    """Prefill: attention output AND the K/V to seed the cache."""
    if positions is None:
        positions = jnp.arange(x.shape[1])
    q, k, v = _project_qkv(p, x, cfg, positions, rope=rope)
    out = ops.flash_attention(q, k, v, causal=True, impl=attn_impl)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    return psum_tp(jnp.einsum("bshk,hkd->bsd", out, p["wo"])), (k, v)


def decode_attention_raw(
    q: jax.Array,        # (B, 1, H, Dh)
    k_cache: jax.Array,  # (B, Smax, KVH, Dh)
    v_cache: jax.Array,
    cache_len: jax.Array,  # scalar int32: number of valid positions (incl. current)
    scale: float,
) -> jax.Array:
    """One-token attention over a (possibly seq-sharded) KV cache."""
    b, _, h, hd = q.shape
    smax, kvh = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, hd).astype(jnp.float32) * scale
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg, k_cache.astype(jnp.float32)
    )  # (B, KVH, G, Smax)
    valid = jnp.arange(smax)[None, None, None, :] < cache_len
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, hd)


def decode_self_attention(
    p: dict,
    x: jax.Array,          # (B, 1, D)
    layer_cache: dict,     # {"k": (B,Smax,KVH,Dh), "v": ...}
    pos: jax.Array,        # scalar int32: index of the current token
    cfg: ModelConfig,
    *,
    rope: bool = True,
) -> tuple[jax.Array, dict]:
    positions = pos[None] if pos.ndim == 0 else pos
    q, k, v = _project_qkv(p, x, cfg, positions, rope)
    kc = jax.lax.dynamic_update_slice(layer_cache["k"], k.astype(layer_cache["k"].dtype), (0, pos, 0, 0))
    vc = jax.lax.dynamic_update_slice(layer_cache["v"], v.astype(layer_cache["v"].dtype), (0, pos, 0, 0))
    kc = constrain(kc, "cache_batch", "cache_seq", "kv_heads", "head_dim")
    vc = constrain(vc, "cache_batch", "cache_seq", "kv_heads", "head_dim")
    out = decode_attention_raw(q, kc, vc, pos + 1, cfg.head_dim ** -0.5)
    out = out.astype(x.dtype)
    o = psum_tp(jnp.einsum("bshk,hkd->bsd", out, p["wo"]))
    return o, {"k": kc, "v": vc}


def _paged_scatter(
    pages: dict, layer: jax.Array, k_rows: jax.Array, v_rows: jax.Array,
    phys: jax.Array, off: jax.Array,
) -> dict:
    """Write per-row K/V (rows, KVH, Dh) into the stacked lane-dense pool
    at ``(layer, phys, off)`` (indices (rows,)), packed as ``ref.pack_kv``
    lays them out. Inside the model's layer loop the pool is the loop's
    carry, so this scatter updates it in place: the only pool-shaped op a
    paged step runs.

    int8 pools (``kv_scale`` present) quantize on the way in — one f32 scale
    per (row, kv head) over head_dim, for K and for V — and scatter the
    scales alongside, so the paged kernels can fuse the dequant.
    Out-of-bounds rows are dropped for values and scales alike."""
    out = dict(pages)
    if "kv_scale" in pages:
        k_rows, k_sc = quantize_kv(k_rows)
        v_rows, v_sc = quantize_kv(v_rows)
        out["kv_scale"] = pages["kv_scale"].at[layer, phys, off].set(
            pack_kv_scales(k_sc, v_sc), mode="drop")
    dtype = pages["kv"].dtype
    out["kv"] = pages["kv"].at[layer, phys, off].set(
        pack_kv(k_rows.astype(dtype), v_rows.astype(dtype)), mode="drop")
    return out


def decode_self_attention_paged(
    p: dict,
    x: jax.Array,            # (S, 1, D) one token per in-flight slot
    pages: dict,             # {"kv": (L,P,page,KVH*2*Dh)} the stacked pool
    layer: jax.Array,        # scalar int32: this layer's index in the pool
    block_tables: jax.Array,  # (S, MP) int32
    lengths: jax.Array,      # (S,) int32 tokens already cached per slot
    cfg: ModelConfig,
    *,
    rope: bool = True,
    attn_impl: str = "xla_chunked",
) -> tuple[jax.Array, dict]:
    """Continuous-batching decode: write the new K/V into each slot's current
    page of this layer, then attend over the block table. Per-slot
    positions (= lengths) drive RoPE, so slots at different depths coexist
    in one batch. Returns (output, the updated pool)."""
    positions = lengths[:, None]  # (S, 1) absolute position of the new token
    q, k, v = _project_qkv(p, x, cfg, positions, rope)
    num_pages, page = pages["kv"].shape[1:3]
    phys = jnp.take_along_axis(
        block_tables, (lengths // page)[:, None], axis=1
    )[:, 0]
    # idle slots (block-table entry 0 = the reserved null page) write out of
    # bounds and are DROPPED: every surviving scatter index is unique, so the
    # update order is well-defined (duplicate-index scatter is not)
    phys = jnp.where(phys == 0, num_pages, phys)
    off = lengths % page
    pages = _paged_scatter(pages, layer, k[:, 0], v[:, 0], phys, off)
    out = ops.paged_attention(
        q[:, 0], pages["kv"], block_tables, lengths + 1, layer,
        kv_scale=pages.get("kv_scale"), scale=cfg.head_dim ** -0.5,
        impl=attn_impl,
    ).astype(x.dtype)  # (S, H_local, Dh)
    # under the serving executor's shard_map, q/kv heads and the page pool
    # are head-sharded: each shard attends its own head slice against its
    # own KV shard (block tables are replicated), and the row-parallel wo
    # partial sums are reduced here
    o = psum_tp(jnp.einsum("bhk,hkd->bd", out, p["wo"]))[:, None, :]
    return o, pages


def prefill_chunk_attention_paged(
    p: dict,
    x: jax.Array,            # (1, C, D) one chunk of ONE sequence's prompt
    pages: dict,             # {"kv": (L,P,page,KVH*2*Dh)} the stacked pool
    layer: jax.Array,        # scalar int32: this layer's index in the pool
    block_table: jax.Array,  # (MP,) int32 the sequence's block-table row
    start: jax.Array,        # scalar int32: positions already in the pages
    valid: jax.Array,        # scalar int32: real (non-padded) chunk tokens
    cfg: ModelConfig,
    *,
    rope: bool = True,
    attn_impl: str = "xla_chunked",
) -> tuple[jax.Array, dict]:
    """Chunked prefill: scatter the chunk's K/V into the sequence's pages
    of this layer, then attend each chunk position over the paged prefix +
    the chunk itself (causal). RoPE uses absolute positions ``start + i``,
    so a chunk never knows (or re-pads to) the full prompt length. Padded
    positions (>= valid) write out of bounds (dropped) and return garbage
    outputs the caller discards.

    ``attn_impl`` selects the attention lowering exactly like decode:
    "pallas"/"auto"-on-TPU dispatches the Pallas chunk-prefill kernel
    (shard-map compatible — each TP shard attends its own head slice of the
    page pool against its grouped-q slice), everything else lowers through
    ``ref.paged_prefill_attention_ref``."""
    c = x.shape[1]
    positions = start + jnp.arange(c)
    q, k, v = _project_qkv(p, x, cfg, positions, rope)
    num_pages, page = pages["kv"].shape[1:3]
    phys = jnp.where(
        jnp.arange(c) < valid, block_table[positions // page], num_pages
    )
    off = positions % page
    pages = _paged_scatter(pages, layer, k[0], v[0], phys, off)
    out = ops.paged_prefill_attention(
        q[0], pages["kv"], block_table, start, valid, layer,
        kv_scale=pages.get("kv_scale"), scale=cfg.head_dim ** -0.5,
        impl=attn_impl,
    ).astype(x.dtype)  # (C, H_local, Dh)
    o = psum_tp(jnp.einsum("chk,hkd->cd", out, p["wo"]))[None]
    return o, pages


def mixed_step_attention_paged(
    p: dict,
    x: jax.Array,             # (R, 1, D) one token per row (decode + chunk)
    pages: dict,              # {"kv": (L,P,page,KVH*2*Dh)} the stacked pool
    layer: jax.Array,         # scalar int32: this layer's index in the pool
    block_tables: jax.Array,  # (R, MP) int32, one block-table row per row
    positions: jax.Array,     # (R,) int32 absolute position per row, -1 = dead
    cfg: ModelConfig,
    *,
    rope: bool = True,
    attn_impl: str = "xla_chunked",
    num_decode: int | None = None,
) -> tuple[jax.Array, dict]:
    """Fused mixed step: decode rows AND one prefill chunk's rows scatter
    their K/V into this layer of the page pool in ONE functional update,
    then every row attends its own block table up to its own position
    (``<= positions[r]``) through ``ops.paged_mixed_attention``.

    A decode slot contributes one row at ``positions[r] = length``; chunk
    token i contributes a row at ``positions[r] = start + i`` sharing the
    chunk slot's block-table row — because the combined scatter lands
    before any row reads, chunk row i sees chunk rows ``< i`` exactly as
    the unfused chunk path does. Dead rows (idle slots, chunk padding) use
    ``positions[r] = -1``: their write is dropped out of bounds and their
    attention output is exact zeros (discarded by the caller).

    Scatter-index uniqueness (the same argument as decode): live rows write
    distinct (page, offset) pairs — decode rows own their writable page
    (``ensure_append_capacity`` COWs shared pages first), chunk rows write
    the chunk slot's exclusively-owned fresh pages (prefix pages are only
    published AFTER the chunk covering them dispatched), and dead rows are
    dropped — so decode/chunk fusion never creates a read-write hazard and
    the dispatch order of the two halves is immaterial.

    ``num_decode`` (static) forwards the mixed-batch structure hint to
    :func:`repro.kernels.ops.paged_mixed_attention`: rows past it are one
    chunk sharing a block-table row, which lets the XLA fallback gather
    the chunk's K/V once instead of per row (the Pallas path ignores it).
    """
    live = positions >= 0
    pos = jnp.maximum(positions, 0)
    q, k, v = _project_qkv(p, x, cfg, pos[:, None], rope)
    num_pages, page = pages["kv"].shape[1:3]
    phys = jnp.take_along_axis(
        block_tables, (pos // page)[:, None], axis=1
    )[:, 0]
    # dead rows and null-page entries write out of bounds and are DROPPED
    phys = jnp.where(live & (phys != 0), phys, num_pages)
    off = pos % page
    pages = _paged_scatter(pages, layer, k[:, 0], v[:, 0], phys, off)
    out = ops.paged_mixed_attention(
        q[:, 0], pages["kv"], block_tables, positions, layer,
        kv_scale=pages.get("kv_scale"), scale=cfg.head_dim ** -0.5,
        impl=attn_impl, num_decode=num_decode,
    ).astype(x.dtype)  # (R, H_local, Dh)
    # same sharding contract as decode: per-shard head slice of q/kv and the
    # page pool, tables/positions replicated, row-parallel wo reduced here
    o = psum_tp(jnp.einsum("bhk,hkd->bd", out, p["wo"]))[:, None, :]
    return o, pages


def cross_attention(
    p: dict,
    x: jax.Array,          # (B, Sq, D) decoder states
    kv: tuple[jax.Array, jax.Array] | None,  # precomputed enc (k, v)
    cfg: ModelConfig,
    *,
    attn_impl: str = "xla_chunked",
) -> jax.Array:
    """Encoder-decoder cross attention (no rope, non-causal)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k, v = kv
    out = ops.flash_attention(q, k, v, causal=False, impl=attn_impl)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_attention_kv(p: dict, enc: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from encoder output (B, Senc, D)."""
    k = jnp.einsum("bsd,dhk->bshk", enc, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc, p["wv"])
    return k, v


def decode_cross_attention(p, x, kv, cfg: ModelConfig, enc_len=None):
    """One-token cross attention over precomputed encoder K/V.

    ``enc_len`` (scalar int32) masks K/V that was zero-padded past the true
    encoder length (the serving cache pads to max_len) — attending over the
    pad would pollute the softmax and diverge from the prefill path. None
    means the K/V is unpadded (use its full length)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k, v = kv
    n = jnp.asarray(k.shape[1], jnp.int32) if enc_len is None else enc_len
    out = decode_attention_raw(
        q, k, v, n, cfg.head_dim ** -0.5
    ).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])
