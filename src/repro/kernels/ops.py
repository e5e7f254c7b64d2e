"""jit'd public wrappers around the Pallas kernels, with XLA references.

Ops: ``flash_attention`` (train/prefill), ``paged_attention`` (single-token
decode over the serving page pool), ``paged_prefill_attention`` (chunked
prefill over the page pool), ``paged_mixed_attention`` (fused decode rows +
one prefill chunk, one dispatch per engine step), ``ssd_scan`` /
``ssd_decode_step`` (Mamba2).

``impl`` selection:
  * "pallas"      — the compiled Pallas TPU kernel. It needs a TPU backend:
                    on any other backend every op raises
                    :class:`PallasUnavailableError` — there is no silent
                    fallback that would hide which device served.
  * "pallas_interpret" — the Pallas kernel in interpret mode on any backend
                    (tests, the differential kernel-fuzz harness, and the
                    kernel-path engine parity suite use this on CPU).
  * "xla_chunked" — pure-jnp chunked implementations from ``ref.py``
                    (bounded memory; the reference lowering the chip smoke
                    compares the kernels against).
  * "naive"       — full-matrix references (tests/small inputs only).
  * "auto"        — "pallas" on TPU backends, else "xla_chunked".

Contract: for every op the ``ref.py`` implementation is the ground truth;
kernels must match it within the tolerance asserted in ``tests/``
(``tests/test_kernel_fuzz.py`` sweeps every kernel against its oracle in
interpret mode: 1e-3 max abs error bound, observed ~1e-6), and
``tests/test_chip_compile.py`` compiles each one for a described TPU v5e.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import (
    paged_attention_bkgd,
    paged_mixed_attention_rkgd,
    paged_prefill_attention_ckgd,
)
from repro.kernels.ssd_scan import ssd_decode_step_bh, ssd_scan_bhsp


class PallasUnavailableError(RuntimeError):
    """``impl="pallas"`` was asked for on a backend with no TPU."""


def _resolve_pallas_impl(impl: str, interpret: bool, op: str) -> tuple[str, bool]:
    """Normalize ``impl``/``interpret`` for every Pallas-backed op.

    "auto" picks the compiled kernel on TPU and the XLA reference
    elsewhere; "pallas_interpret" forces the kernel through the interpreter
    (any backend); plain compiled "pallas" off-TPU raises, uniformly across
    ops, so a run that asked for the kernels either gets them or stops.
    """
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = "pallas" if on_tpu else "xla_chunked"
    if impl == "pallas_interpret":
        return "pallas", True
    if impl == "pallas" and not interpret and not on_tpu:
        raise PallasUnavailableError(
            f"{op}: impl='pallas' needs a TPU backend, have "
            f"{jax.default_backend()!r} (use impl='auto' for the XLA "
            f"reference off-TPU, or 'pallas_interpret' to run the kernel "
            f"interpreted)"
        )
    return impl, interpret


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, KVH, D)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    impl: str = "auto",
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Multi-head / grouped-query attention. Returns (B, Sq, H, D)."""
    impl, interpret = _resolve_pallas_impl(impl, interpret, "flash_attention")
    if impl == "naive":
        return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
    if impl == "xla_chunked":
        return ref.flash_attention_chunked(
            q, k, v, causal=causal, scale=scale, chunk_kv=block_kv
        )
    if impl == "pallas":
        qt = jnp.swapaxes(q, 1, 2)  # (B, H, S, D)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        out = flash_attention_bhsd(
            qt, kt, vt,
            causal=causal, scale=scale,
            block_q=block_q, block_kv=block_kv,
            interpret=interpret,
        )
        return jnp.swapaxes(out, 1, 2)
    raise ValueError(f"unknown attention impl {impl!r}")


def _kv_heads(q, kv_pages) -> int:
    """The pool's (local) kv head count: its lanes over the (K, V) pair's
    2*D, checked against q's head count."""
    h, d = q.shape[-2:]
    kvh = kv_pages.shape[-1] // (2 * d)
    assert kvh and h % kvh == 0, (
        f"q heads ({h}) must be a multiple of kv heads ({kvh}) — a sharded "
        f"caller must slice both by the same tensor-parallel degree"
    )
    return kvh


def _layer_view(q, kv_pages, kv_scale, layer):
    """The XLA lowering's logical view of one layer of the stacked
    lane-dense pool: (K, V), each (P, page, KVH, D), dequantized when
    ``kv_scale`` is given (``ref.unpack_kv``)."""
    _kv_heads(q, kv_pages)
    return ref.unpack_kv(
        kv_pages[layer], q.shape[-1],
        None if kv_scale is None else kv_scale[layer],
    )


def paged_attention(
    q: jax.Array,             # (B, H, D) one query token per sequence
    kv_pages: jax.Array,      # (L, P, page, KVH*2*D) stacked lane-dense pool
    block_tables: jax.Array,  # (B, MP) int32
    lengths: jax.Array,       # (B,) int32 valid positions per sequence
    layer: jax.Array,         # scalar int32: the pool's layer to read
    *,
    kv_scale: jax.Array | None = None,  # (L, P, page, KVH*2) int8 scales
    scale: float | None = None,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Single-token decode attention over a paged KV cache. Returns (B, H, D).

    Idle slots (length 0) return zeros rather than NaN, so a continuous
    batcher can keep dead rows in the decode batch.

    The pool is the serving cache's own array, stacked over layers and
    lane-dense (``kernels/paged_attention.py``); ``layer`` picks the layer.
    The Pallas kernel reads that layer's pages straight from the stacked
    pool; the XLA path reads the logical view :func:`ref.unpack_kv` gives
    and runs the unchanged fp32 oracle, so both lowerings share one
    ground truth. Quantized pools: with ``kv_scale`` the pages are int8
    with one f32 scale per (page, position, kv head) for K and for V; the
    Pallas path fuses dequant into the page load, the XLA path
    dequantizes through ``ref.unpack_kv``.

    Shard-local contract (sharded serving): under the executor's
    ``shard_map`` this op receives the PER-SHARD head slice — q carries
    ``H/tp`` heads, the pool carries ``KVH/tp`` kv heads' lanes — while
    ``block_tables``/``lengths``/``layer`` are replicated (page ids are
    shard-invariant). Heads shard in contiguous GQA groups, so the grouped
    reshape below is exactly the local slice's own grouping and every impl
    (Pallas and the XLA refs) works unchanged on the slice; the q/kv head
    ratio must survive the slicing, which the divisibility check asserts.
    """
    impl, interpret = _resolve_pallas_impl(impl, interpret, "paged_attention")
    b, h, d = q.shape
    if impl in ("naive", "xla_chunked"):
        k, v = _layer_view(q, kv_pages, kv_scale, layer)
        return ref.paged_attention_ref(
            q, k, v, block_tables, lengths, scale=scale
        )
    if impl == "pallas":
        kvh = _kv_heads(q, kv_pages)
        out = paged_attention_bkgd(
            q.reshape(b, kvh, h // kvh, d), kv_pages, block_tables, lengths,
            layer, kv_scale=kv_scale, scale=scale, interpret=interpret,
        )
        return out.reshape(b, h, d)
    raise ValueError(f"unknown paged attention impl {impl!r}")


def paged_prefill_attention(
    q: jax.Array,            # (C, H, D) one prefill chunk of ONE sequence
    kv_pages: jax.Array,     # (L, P, page, KVH*2*D) stacked lane-dense pool
    block_table: jax.Array,  # (MP,) int32 the sequence's block-table row
    start: jax.Array,        # scalar int32: positions already cached
    valid: jax.Array,        # scalar int32: real tokens in this chunk
    layer: jax.Array,        # scalar int32: the pool's layer to read
    *,
    kv_scale: jax.Array | None = None,  # (L, P, page, KVH*2) int8 scales
    scale: float | None = None,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Chunked-prefill attention over a paged KV cache. Returns (C, H, D).

    The chunk's own K/V must already be scattered into the pages; query i
    (absolute position ``start + i``) attends causally to every cached
    position ``<= start + i`` through the block table, and padded queries
    (``i >= valid``) return zeros. The Pallas kernel
    (:func:`repro.kernels.paged_attention.paged_prefill_attention_ckgd`)
    mirrors the decode kernel's pool and shard-local contract (see
    :func:`paged_attention`), and ``ref.paged_prefill_attention_ref`` stays
    the oracle and the CPU path.
    """
    impl, interpret = _resolve_pallas_impl(
        impl, interpret, "paged_prefill_attention"
    )
    c, h, d = q.shape
    if impl in ("naive", "xla_chunked"):
        k, v = _layer_view(q, kv_pages, kv_scale, layer)
        return ref.paged_prefill_attention_ref(
            q, k, v, block_table, start, valid, scale=scale
        )
    if impl == "pallas":
        kvh = _kv_heads(q, kv_pages)
        out = paged_prefill_attention_ckgd(
            q.reshape(c, kvh, h // kvh, d), kv_pages, block_table, start,
            valid, layer, kv_scale=kv_scale, scale=scale,
            interpret=interpret,
        )
        return out.reshape(c, h, d)
    raise ValueError(f"unknown paged prefill impl {impl!r}")


def paged_mixed_attention(
    q: jax.Array,             # (R, H, D) one query row per batch row
    kv_pages: jax.Array,      # (L, P, page, KVH*2*D) stacked lane-dense pool
    block_tables: jax.Array,  # (R, MP) int32, one block-table row per row
    last_pos: jax.Array,      # (R,) int32 last attendable position, -1 = dead
    layer: jax.Array,         # scalar int32: the pool's layer to read
    *,
    kv_scale: jax.Array | None = None,  # (L, P, page, KVH*2) int8 scales
    scale: float | None = None,
    impl: str = "auto",
    interpret: bool = False,
    num_decode: int | None = None,
) -> jax.Array:
    """Fused mixed-step attention over a paged KV cache. Returns (R, H, D).

    Rows are independent: a decode row carries its own slot's block-table
    row with ``last_pos = length`` (the just-scattered token), a prefill
    chunk contributes C consecutive rows sharing one block-table row with
    ``last_pos = start + i`` for live rows, and padded rows (idle slots,
    chunk rows past ``valid``) use ``last_pos = -1`` and return exact
    zeros. One engine step therefore needs ONE attention dispatch. The
    Pallas kernel (:func:`repro.kernels.paged_attention.
    paged_mixed_attention_rkgd`) keeps the other paged kernels' pool and
    shard-local contract (see :func:`paged_attention`), and
    ``ref.paged_mixed_attention_ref`` is the oracle and the CPU path.

    ``num_decode`` is an OPTIONAL static structure hint: when set, the
    caller asserts rows ``[num_decode, R)`` form one prefill chunk — every
    row repeats the same block-table row, live rows hold contiguous
    positions ``start + i`` and dead rows are a suffix. The XLA path
    then evaluates decode rows through :func:`ref.paged_attention_ref` and
    chunk rows through :func:`ref.paged_prefill_attention_ref`, gathering
    the chunk's K/V ONCE instead of once per chunk row (the generic ref
    materializes (R, MP*page) keys, which duplicates the shared table C
    times — ruinous off-TPU). The Pallas kernel is row-generic and ignores
    the hint; the generic ref stays the oracle the fuzz harness compares
    both lowerings against.
    """
    impl, interpret = _resolve_pallas_impl(
        impl, interpret, "paged_mixed_attention"
    )
    r, h, d = q.shape
    if impl in ("naive", "xla_chunked"):
        k, v = _layer_view(q, kv_pages, kv_scale, layer)
        if num_decode is None or not 0 < num_decode < r:
            return ref.paged_mixed_attention_ref(
                q, k, v, block_tables, last_pos, scale=scale
            )
        s = num_decode
        dec = ref.paged_attention_ref(
            q[:s], k, v, block_tables[:s], last_pos[:s] + 1, scale=scale,
        )
        # dead chunk rows are a suffix, so the live count and the cursor
        # fall out of last_pos; valid == 0 masks every chunk row to zeros
        valid = jnp.sum(last_pos[s:] >= 0).astype(jnp.int32)
        start = jnp.maximum(last_pos[s], 0)
        chk = ref.paged_prefill_attention_ref(
            q[s:], k, v, block_tables[s], start, valid, scale=scale,
        )
        return jnp.concatenate([dec, chk], axis=0)
    if impl == "pallas":
        kvh = _kv_heads(q, kv_pages)
        out = paged_mixed_attention_rkgd(
            q.reshape(r, kvh, h // kvh, d), kv_pages, block_tables, last_pos,
            layer, kv_scale=kv_scale, scale=scale, interpret=interpret,
        )
        return out.reshape(r, h, d)
    raise ValueError(f"unknown paged mixed impl {impl!r}")


# ---------------------------------------------------------------------------
# Mamba2 SSD
# ---------------------------------------------------------------------------


def ssd_scan(
    x: jax.Array,   # (B, S, H, P)
    dt: jax.Array,  # (B, S, H)
    A: jax.Array,   # (H,)
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    *,
    chunk: int = 256,
    impl: str = "auto",
    interpret: bool = False,
    init_state: jax.Array | None = None,  # (B, H, P, N) f32
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y (B,S,H,P), final_state (B,H,P,N) f32).

    S need not divide ``chunk``: the tail is padded with dt=0 positions,
    which are exact identities on the recurrence (decay exp(0·a)=1, update
    dt·x=0), so one chunked dispatch covers any length and the padded
    outputs are simply sliced off. ``init_state`` continues a scan from a
    carried state (chunked prefill): the reference paths thread it
    natively; the Pallas kernel always starts from zeros, so its linear
    contribution — y_t += C_t·(e^{Σ≤t dA} h0), fs += e^{Σ dA} h0 — is
    superposed in closed form on top of the kernel output.
    """
    impl, interpret = _resolve_pallas_impl(impl, interpret, "ssd_scan")
    if impl == "naive":
        return ref.ssd_sequential(x, dt, A, Bm, Cm, init_state=init_state)

    s = x.shape[1]
    chunk_eff = min(chunk, s)
    pad = (chunk_eff - s % chunk_eff) % chunk_eff
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))

    if impl == "xla_chunked":
        y, fs = ref.ssd_chunked(x, dt, A, Bm, Cm, init_state, chunk=chunk_eff)
        return (y[:, :s] if pad else y), fs
    if impl == "pallas":
        xt = jnp.swapaxes(x, 1, 2)    # (B, H, S, P)
        dtt = jnp.swapaxes(dt, 1, 2)  # (B, H, S)
        y, fs = ssd_scan_bhsp(xt, dtt, A, Bm, Cm, chunk=chunk_eff,
                              interpret=interpret)
        y = jnp.swapaxes(y, 1, 2)[:, :s]
        if init_state is not None:
            h0 = init_state.astype(jnp.float32)
            dA = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]
            cs = jnp.cumsum(dA, axis=1)  # (B, S+pad, H)
            proj = jnp.einsum(
                "bsn,bhpn->bshp", Cm[:, :s].astype(jnp.float32), h0
            )
            y = (
                y.astype(jnp.float32) + jnp.exp(cs[:, :s, :, None]) * proj
            ).astype(x.dtype)
            fs = fs + jnp.exp(cs[:, -1])[..., None, None] * h0
        return y, fs
    raise ValueError(f"unknown ssd impl {impl!r}")


def ssd_decode_step(
    state: jax.Array,  # (B, H, P, N) f32
    x_t: jax.Array,    # (B, H, P)
    dt_t: jax.Array,   # (B, H)
    A: jax.Array,      # (H,)
    B_t: jax.Array,    # (B, N)
    C_t: jax.Array,    # (B, N)
    *,
    impl: str = "auto",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Single-token SSD recurrence. Returns (y (B,H,P), new_state f32)."""
    impl, interpret = _resolve_pallas_impl(impl, interpret, "ssd_decode_step")
    if impl in ("naive", "xla_chunked"):
        return ref.ssd_decode_step(state, x_t, dt_t, A, B_t, C_t)
    if impl == "pallas":
        return ssd_decode_step_bh(state, x_t, dt_t, A, B_t, C_t,
                                  interpret=interpret)
    raise ValueError(f"unknown ssd decode impl {impl!r}")
