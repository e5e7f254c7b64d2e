"""Paged KV cache + paged attention + continuous batching engine.

Covers the satellite checklist: page alloc/free/reuse, block-table
correctness vs. the dense cache, paged-attention-vs-reference numerical
parity (including the Pallas kernel in interpret mode), and end-to-end
engine equivalence with the lockstep baseline.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.kernels import ops, ref
from repro.models import build_model
from repro.serving import (
    ContinuousBatchingEngine,
    GenerationEngine,
    PagedKVCache,
    PagePool,
    Request,
)
from repro.serving.kv_cache import NULL_PAGE, cdiv, write_prefill_pages


def assert_drained(cache):
    """Every page is either free or parked (zero-refcount prefix pages kept
    for reuse by the tier manager) once all sequences have released."""
    assert cache.pool.available + cache.parked_count == cache.num_pages - 1
    assert (cache.pool.refcounts[1:] == 0).all()


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------


def test_page_pool_alloc_free_reuse():
    pool = PagePool(8)  # pages 1..7 usable, 0 reserved
    assert pool.available == 7
    a = pool.alloc(3)
    assert len(set(a)) == 3 and NULL_PAGE not in a
    b = pool.alloc(4)
    assert pool.available == 0
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(1)
    pool.free(a)
    assert pool.available == 3
    c = pool.alloc(3)
    assert set(c) == set(a)  # freed pages are reused
    assert not set(c) & set(b)


def test_paged_cache_block_table_bookkeeping():
    cache = PagedKVCache(
        num_layers=1, num_kv_heads=1, head_dim=4, dtype=jnp.float32,
        max_slots=2, max_context=32, page_size=8,
    )
    slot, cached = cache.admit(context_len=10)  # needs 2 pages
    assert cached == 0  # no prompt tokens given -> nothing shared
    pages = cache._slot_pages[slot]
    assert len(pages) == 2
    assert list(cache.block_tables[slot, :2]) == pages
    assert (cache.block_tables[slot, 2:] == NULL_PAGE).all()

    # appending through position 15 stays inside page 2; 16 allocates page 3
    for _ in range(6):
        cache.ensure_append_capacity(slot)
        cache.append(slot)
    assert len(cache._slot_pages[slot]) == 2
    cache.ensure_append_capacity(slot)
    assert len(cache._slot_pages[slot]) == 3

    avail = cache.pool.available
    cache.release(slot)
    assert cache.pool.available == avail + 3
    assert (cache.block_tables[slot] == NULL_PAGE).all()
    assert cache.lengths[slot] == 0


# ---------------------------------------------------------------------------
# paged attention numerics
# ---------------------------------------------------------------------------


def _random_paged_case(rng, b=3, h=4, kvh=2, d=16, page=8, mp=4):
    num_pages = b * mp + 1
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    lens = np.array([0] + list(rng.integers(1, mp * page + 1, b - 1)), np.int32)
    bt = np.full((b, mp), NULL_PAGE, np.int32)
    nxt = 1
    for i in range(b):
        for p in range(cdiv(int(lens[i]), page)):
            bt[i, p] = nxt
            nxt += 1
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens)


def test_paged_attention_ref_matches_dense(rng):
    q, kp, vp, bt, lens = _random_paged_case(rng)
    out = ref.paged_attention_ref(q, kp, vp, bt, lens)
    assert (np.asarray(out[0]) == 0).all()  # idle slot -> zeros, not NaN
    page = kp.shape[1]
    for b in range(q.shape[0]):
        n = int(lens[b])
        if n == 0:
            continue
        kd = np.stack([np.asarray(kp)[bt[b, j // page], j % page] for j in range(n)])
        vd = np.stack([np.asarray(vp)[bt[b, j // page], j % page] for j in range(n)])
        dense = ref.flash_attention_ref(
            q[b][None, None], jnp.asarray(kd)[None], jnp.asarray(vd)[None],
            causal=False,
        )[0, 0]
        np.testing.assert_allclose(
            np.asarray(out[b]), np.asarray(dense), atol=1e-5, rtol=1e-5
        )


def test_paged_attention_pallas_matches_ref(rng):
    """Acceptance: kernel vs reference <= 1e-3 max abs error (interpret)."""
    for seed in range(3):
        r = np.random.default_rng(seed)
        q, kp, vp, bt, lens = _random_paged_case(r)
        pool = ref.pack_kv(kp, vp)[None]
        o_ref = ops.paged_attention(q, pool, bt, lens, 0, impl="xla_chunked")
        o_pal = ops.paged_attention(
            q, pool, bt, lens, 0, impl="pallas", interpret=True
        )
        err = float(jnp.abs(o_ref - o_pal).max())
        assert err <= 1e-3, err


def test_paged_attention_gqa_and_mqa(rng):
    for kvh in (1, 4):
        q, kp, vp, bt, lens = _random_paged_case(rng, h=4, kvh=kvh)
        pool = ref.pack_kv(kp, vp)[None]
        o_ref = ops.paged_attention(q, pool, bt, lens, 0, impl="xla_chunked")
        o_pal = ops.paged_attention(
            q, pool, bt, lens, 0, impl="pallas", interpret=True
        )
        assert float(jnp.abs(o_ref - o_pal).max()) <= 1e-3


def test_paged_prefill_pallas_matches_ref(rng):
    """Acceptance: chunk-prefill kernel vs oracle <= 1e-3 (interpret mode).
    The exhaustive shape sweep lives in ``test_kernel_fuzz.py``; this pins
    the canonical serving shape (chunk straddling a page, partial history)."""
    c, h, kvh, d, page = 8, 4, 2, 16, 8
    start, valid = 5, 8
    num_pages = 4
    q = jnp.asarray(rng.standard_normal((c, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    bt = jnp.asarray([2, 1, 3], jnp.int32)
    args = (q, ref.pack_kv(kp, vp)[None], bt, jnp.int32(start),
            jnp.int32(valid), 0)
    o_ref = ops.paged_prefill_attention(*args, impl="xla_chunked")
    o_pal = ops.paged_prefill_attention(*args, impl="pallas_interpret")
    assert float(jnp.abs(o_ref - o_pal).max()) <= 1e-3


# ---------------------------------------------------------------------------
# paged model path vs dense cache
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smollm():
    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def test_prefill_pages_match_dense_cache(smollm):
    """Block-table scatter reproduces the dense prefill KV exactly."""
    cfg, model, params = smollm
    plen, bucket = 11, 16
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :plen] = np.arange(1, plen + 1)
    cache, _ = jax.jit(lambda p, b: model.prefill(p, b, bucket))(
        params, {"tokens": jnp.asarray(toks)}
    )

    paged = PagedKVCache(
        num_layers=cfg.num_layers, num_kv_heads=cfg.eff_kv_heads,
        head_dim=cfg.head_dim, dtype=jnp.dtype(cfg.dtype),
        max_slots=2, max_context=32, page_size=4,
    )
    slot, _ = paged.admit(context_len=plen)
    paged.swap_pages(write_prefill_pages(
        dict(paged.pages), cache["k"][:, 0], cache["v"][:, 0],
        paged.device_row(slot), jnp.asarray(plen, jnp.int32),
    ))
    got_k, got_v = paged.gather_dense(slot)
    np.testing.assert_array_equal(got_k, np.asarray(cache["k"][:, 0, :plen]))
    np.testing.assert_array_equal(got_v, np.asarray(cache["v"][:, 0, :plen]))


def test_decode_step_paged_matches_dense(smollm):
    """Paged decode logits == dense decode logits for the same sequence."""
    cfg, model, params = smollm
    plen, steps, max_len = 7, 5, 32
    prompt = np.arange(1, plen + 1, dtype=np.int32)

    # dense path; record the token fed at each step so the paged path sees
    # the IDENTICAL stream (an argmax near-tie must not fork the comparison)
    batch = {"tokens": jnp.asarray(prompt[None])}
    dcache, dlogits = jax.jit(lambda p, b: model.prefill(p, b, max_len))(
        params, batch
    )
    dense_logits = [np.asarray(dlogits[0])]
    fed_tokens = []
    tok = jnp.argmax(dlogits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    for _ in range(steps):
        fed_tokens.append(int(tok[0]))
        dcache, dlogits = model.decode_step(params, dcache, tok[:, None])
        dense_logits.append(np.asarray(dlogits[0]))
        tok = jnp.argmax(dlogits[:, : cfg.vocab_size], -1).astype(jnp.int32)

    # paged path (slot 1 of 3, other slots idle)
    paged = PagedKVCache(
        num_layers=cfg.num_layers, num_kv_heads=cfg.eff_kv_heads,
        head_dim=cfg.head_dim, dtype=jnp.dtype(cfg.dtype),
        max_slots=3, max_context=max_len, page_size=4,
    )
    slot, _ = paged.admit(context_len=plen)
    pcache, plogits = jax.jit(
        lambda p, b, i: model.prefill(p, b, plen, logits_index=i)
    )(params, batch, jnp.asarray(plen - 1, jnp.int32))
    paged.swap_pages(write_prefill_pages(
        dict(paged.pages), pcache["k"][:, 0], pcache["v"][:, 0],
        paged.device_row(slot), jnp.asarray(plen, jnp.int32),
    ))
    np.testing.assert_allclose(
        np.asarray(plogits[0]), dense_logits[0], atol=1e-4, rtol=1e-4
    )

    pages = dict(paged.pages)
    for i in range(steps):
        paged.ensure_append_capacity(slot)
        tokens = np.zeros((3, 1), np.int32)
        tokens[slot, 0] = fed_tokens[i]
        bt, lens = paged.device_tables()
        pages, logits = model.decode_step_paged(
            params, pages, bt, lens, jnp.asarray(tokens)
        )
        paged.append(slot)
        np.testing.assert_allclose(
            np.asarray(logits[slot]), dense_logits[i + 1], atol=1e-4, rtol=1e-4
        )
        assert np.isfinite(np.asarray(logits)).all()  # idle slots too


# ---------------------------------------------------------------------------
# page pool layout: what the writers pack, the unpack view reads back
# ---------------------------------------------------------------------------

LAYOUT = dict(num_layers=3, num_kv_heads=2, head_dim=8, dtype=jnp.bfloat16,
              max_slots=2, max_context=64, page_size=8, num_pages=8)


def _stored(rows, quant):
    """What a pool reads back for written f32 rows (..., KVH, D): the bf16
    value, or for an int8 pool the row quantized and dequantized."""
    rows = jnp.asarray(rows)
    if quant == "int8":
        return np.asarray(ref.dequantize_pages(*ref.quantize_kv(rows)))
    return np.asarray(rows.astype(jnp.bfloat16).astype(jnp.float32))


def _write(cache, slot, writer, n, seed, start=0):
    """Write random K/V for positions [start, n) of ``slot`` into every
    layer through one of the pool's writers: the model's per-layer row
    write (``attention._paged_scatter``, with a dropped out-of-bounds row)
    or the whole-prompt ``write_prefill_pages`` (from position 0, with
    padded positions). Returns the logical (L, n - start, KVH, D) K and V
    written."""
    from repro.models.attention import _paged_scatter

    rng = np.random.default_rng(seed)
    shape = (LAYOUT["num_layers"], n - start, LAYOUT["num_kv_heads"],
             LAYOUT["head_dim"])
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    row = cache.block_tables[slot]
    page = cache.page_size
    if writer == "prefill":  # three padded positions, to be dropped
        assert start == 0
        pad = np.full(shape[:1] + (3,) + shape[2:], 7, np.float32)
        cache.swap_pages(write_prefill_pages(
            dict(cache.pages), jnp.asarray(np.concatenate([k, pad], 1)),
            jnp.asarray(np.concatenate([v, pad], 1)), jnp.asarray(row),
            jnp.asarray(n, jnp.int32),
        ))
        return k, v
    pos = np.arange(start, n + 1)
    phys = np.where(pos < n, row[np.minimum(pos, n - 1) // page],
                    cache.num_pages)  # the last row is out of bounds
    pages = dict(cache.pages)
    for layer in range(shape[0]):
        pages = _paged_scatter(
            pages, jnp.int32(layer),
            jnp.asarray(np.concatenate([k[layer], k[layer, :1] + 7])),
            jnp.asarray(np.concatenate([v[layer], v[layer, :1] + 7])),
            jnp.asarray(phys, jnp.int32), jnp.asarray(pos % page, jnp.int32))
    cache.swap_pages(pages)
    return k, v


def _read(cache, pages, n):
    """Positions [0, n) of a page list through the unpack view:
    (L, n, KVH, D) K and V."""
    k, v = (np.asarray(a) for a in cache.unpacked())
    page = cache.page_size
    idx = [(pages[i // page], i % page) for i in range(n)]
    return (np.stack([k[:, p, o] for p, o in idx], axis=1),
            np.stack([v[:, p, o] for p, o in idx], axis=1))


@pytest.mark.parametrize("writer", ["row", "prefill"])
@pytest.mark.parametrize("quant", ["none", "int8"])
def test_pool_layout_writers_roundtrip(quant, writer):
    """A row written through each writer reads back equal through the
    unpack view at every (layer, page, offset, head), K and V apart; the
    dropped rows land nowhere (every other page stays zero)."""
    cache = PagedKVCache(quant=quant, **LAYOUT)
    n = 13  # one full page and a partial one
    slot, _ = cache.admit(n)
    k, v = _write(cache, slot, writer, n, seed=1)
    got_k, got_v = _read(cache, cache._slot_pages[slot], n)
    np.testing.assert_array_equal(got_k, _stored(k, quant))
    np.testing.assert_array_equal(got_v, _stored(v, quant))
    all_k, all_v = (np.asarray(a) for a in cache.unpacked())
    others = [p for p in range(cache.num_pages)
              if p not in cache._slot_pages[slot]]
    assert not all_k[:, others].any() and not all_v[:, others].any()
    assert not all_k[:, cache._slot_pages[slot][1], n % 8:].any()


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_pool_layout_survives_cow_copy(quant):
    """COW's page copy moves the packed page whole: the copy reads back
    equal to its source at every (layer, offset, head), and a row written
    into the copy leaves the source untouched."""
    cache = PagedKVCache(quant=quant, **LAYOUT)
    n = 13
    a, _ = cache.admit(n)
    k, v = _write(cache, a, "row", n, seed=2)
    b = cache.fork(a)
    assert cache.ensure_append_capacity(b)  # position 13: the tail page
    pa, pb = cache._slot_pages[a], cache._slot_pages[b]
    assert pa[1] != pb[1] and cache.stats["cow_copies"] == 1
    got_k, got_v = _read(cache, pb, n)
    np.testing.assert_array_equal(got_k, _stored(k, quant))
    np.testing.assert_array_equal(got_v, _stored(v, quant))
    k2, v2 = _write(cache, b, "row", n + 1, seed=3, start=8)  # b's copy
    got_k, got_v = _read(cache, pb, n + 1)
    np.testing.assert_array_equal(got_k[:, 8:], _stored(k2, quant))
    np.testing.assert_array_equal(got_v[:, 8:], _stored(v2, quant))
    src_k, src_v = _read(cache, pa, n)
    np.testing.assert_array_equal(src_k, _stored(k, quant))
    np.testing.assert_array_equal(src_v, _stored(v, quant))


@pytest.mark.parametrize("quant", ["none", "int8"])
def test_pool_layout_survives_spill_revive(quant):
    """A parked prefix spilled to host RAM and prefetched back into other
    device pages reads back equal through the unpack view."""
    from repro.serving import KVTierManager

    tiers = KVTierManager(host_pages=8)
    cache = PagedKVCache(quant=quant, tiers=tiers, **LAYOUT)
    toks = list(range(50, 75))  # 3 full pages + a tail
    slot, _ = cache.admit(len(toks), toks)
    k, v = _write(cache, slot, "row", len(toks), seed=4)
    cache.register_prefix(slot, toks, len(toks))
    chain = list(cache._slot_pages[slot][:3])
    cache.release(slot)
    big, _ = cache.admit(41, list(range(300, 341)))  # spills the chain
    assert tiers.counters["spilled_pages"] == 3
    cache.release(big)
    assert not cache.can_admit(len(toks), toks)  # prefetch dispatched
    cache.tick_tiers()
    slot2, cached = cache.admit(len(toks), toks)
    assert cached == 24
    assert cache._slot_pages[slot2][:3] != chain  # revived elsewhere
    got_k, got_v = _read(cache, cache._slot_pages[slot2], 24)
    np.testing.assert_array_equal(got_k, _stored(k, quant)[:, :24])
    np.testing.assert_array_equal(got_v, _stored(v, quant)[:, :24])


# ---------------------------------------------------------------------------
# continuous batching engine
# ---------------------------------------------------------------------------


def test_continuous_engine_matches_lockstep(smollm):
    """Greedy decode through the continuous batcher must equal the lockstep
    engine run one request at a time (the exact, no-padding baseline)."""
    cfg, model, params = smollm
    rng = np.random.default_rng(2)
    reqs = [
        Request(
            f"r{i}",
            list(rng.integers(1, cfg.vocab_size, rng.integers(3, 30))),
            max_new_tokens=int(rng.integers(1, 10)),
        )
        for i in range(7)
    ]
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=3,
                                   page_size=8)
    out = eng.generate(reqs)
    base = GenerationEngine(cfg, params, max_len=64)
    for r, o in zip(reqs, out):
        exact = base.generate([Request(r.uid, r.prompt, r.max_new_tokens)])[0]
        assert o.uid == r.uid
        assert o.tokens == exact.tokens, r.uid
        assert len(o.tokens) == r.max_new_tokens
    # all pages returned to the pool (or parked for prefix reuse)
    assert_drained(eng.cache)
    assert eng.cache.free_slot_count == eng.max_slots


def test_continuous_engine_per_request_temperature(smollm):
    cfg, model, params = smollm
    reqs = [
        Request("greedy", [1, 2, 3], max_new_tokens=6, temperature=0.0),
        Request("hot", [1, 2, 3], max_new_tokens=6, temperature=1.0),
    ]
    eng = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=2,
                                   page_size=8, seed=7)
    out = {r.uid: r for r in eng.generate(reqs)}
    base = GenerationEngine(cfg, params, max_len=32)
    exact = base.generate([Request("greedy", [1, 2, 3], 6)])[0]
    # greedy row unaffected by the hot row's sampling
    assert out["greedy"].tokens == exact.tokens
    assert len(out["hot"].tokens) == 6


def test_lockstep_per_request_temperature(smollm):
    """Satellite fix: greedy rows stay greedy when batched with hot rows."""
    cfg, model, params = smollm
    base = GenerationEngine(cfg, params, max_len=32)
    exact = base.generate([Request("g", [1, 2, 3], 6)])[0]
    mixed = base.generate([
        Request("g", [1, 2, 3], 6, temperature=0.0),
        Request("h", [1, 2, 3], 6, temperature=1.0),
    ])
    assert mixed[0].tokens == exact.tokens


def test_engine_preempts_under_pool_pressure(smollm):
    """A too-small page pool forces preemption, never a crash or a hang,
    and preempted (regenerated) greedy outputs stay exact."""
    cfg, model, params = smollm
    eng = ContinuousBatchingEngine(cfg, params, max_len=40, max_slots=2,
                                   page_size=8, num_pages=6)
    # distinct prompts: prefix sharing must not relieve the pool pressure
    reqs = [Request(f"p{i}", [100 + i] + list(range(2, 15)), max_new_tokens=10)
            for i in range(3)]
    out = eng.generate(reqs)
    assert eng.stats["preemptions"] > 0
    base = GenerationEngine(cfg, params, max_len=40)
    for r, o in zip(reqs, out):
        exact = base.generate([Request(r.uid, r.prompt, r.max_new_tokens)])[0]
        assert o.tokens == exact.tokens
    assert_drained(eng.cache)


def test_engine_rejects_unschedulable_request(smollm):
    cfg, model, params = smollm
    eng = ContinuousBatchingEngine(cfg, params, max_len=40, max_slots=2,
                                   page_size=8, num_pages=4)
    with pytest.raises(ValueError, match="never be scheduled"):
        eng.enqueue(Request("never", list(range(1, 31)), max_new_tokens=10))
    with pytest.raises(ValueError, match="max_len"):
        eng.enqueue(Request("long", list(range(1, 40)), max_new_tokens=10))


def test_bus_poison_message_is_rejected_and_committed(smollm, tmp_path):
    """An unservable bus message must be committed (not redelivered forever)
    and recorded as a rejection, while later messages still serve."""
    from repro.core import TopicBus

    cfg, model, params = smollm
    bus = TopicBus(tmp_path)
    bus.publish("requests", {"uid": "bad", "prompt": list(range(40)),
                             "max_new_tokens": 16})
    bus.publish("requests", {"uid": "good", "prompt": [1, 2, 3],
                             "max_new_tokens": 3})
    eng = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=2,
                                   page_size=8)
    n = eng.admit_from_bus(bus, "requests", "g0")
    assert n == 1
    assert bus.lag("requests", "g0") == 0  # poison message consumed
    assert eng.stats["rejected"] == 1
    (uid, err), = eng.drain_rejections()
    assert uid == "bad" and "max_len" in err
    served = []
    while not eng.idle:
        served.extend(ev.uid for ev in eng.step() if ev.kind == "finish")
    assert served == ["good"]


# ---------------------------------------------------------------------------
# prefix sharing / copy-on-write
# ---------------------------------------------------------------------------


def _small_cache(**kw):
    args = dict(num_layers=1, num_kv_heads=1, head_dim=4, dtype=jnp.float32,
                max_slots=3, max_context=32, page_size=8)
    args.update(kw)
    return PagedKVCache(**args)


def test_match_prefix_capped_below_last_token():
    """A prompt equal to its cached prefix must still recompute >= 1 token
    (the engine needs its logits), so matching stops strictly before the
    last token even on a page boundary."""
    cache = _small_cache()
    toks = list(range(100, 116))  # exactly 2 full pages
    slot, cached = cache.admit(len(toks), toks)
    assert cached == 0
    cache.register_prefix(slot, toks, len(toks))
    # identical prompt: only page 0 is eligible (page 1 holds the last token)
    _, cached2 = cache.match_prefix(toks)
    assert cached2 == 8
    # a longer prompt extending the prefix can use both full pages
    _, cached3 = cache.match_prefix(toks + [1, 2, 3])
    assert cached3 == 16


def test_shared_prefix_pages_not_double_freed():
    """Two slots sharing prefix pages release independently; the shared
    page survives the first release and every refcount returns to zero."""
    cache = _small_cache()
    toks = list(range(1, 21))  # 20 tokens: 2 full pages + 1 partial
    a, cached_a = cache.admit(len(toks), toks)
    assert cached_a == 0 and len(cache._slot_pages[a]) == 3
    cache.register_prefix(a, toks, len(toks))

    b, cached_b = cache.admit(len(toks), toks)
    assert cached_b == 16  # both full pages shared
    shared = cache._slot_pages[b][:2]
    assert shared == cache._slot_pages[a][:2]
    assert all(cache.pool.refcounts[p] == 2 for p in shared)

    avail = cache.pool.available
    cache.release(a)
    # a's private tail page freed; the two shared pages survive for b
    assert cache.pool.available == avail + 1
    assert all(cache.pool.refcounts[p] == 1 for p in shared)
    # b can still resolve its prefix through the index
    assert cache.match_prefix(toks + [99])[1] == 16
    cache.release(b)
    assert cache.pool.available == cache.num_pages - 1
    assert (cache.pool.refcounts[1:] == 0).all()
    assert not cache._prefix_index  # freed pages leave the index


def test_fork_cow_copies_exactly_one_page():
    """A write after fork copies exactly the written page; the other pages
    stay shared and the source slot's data is untouched."""
    cache = _small_cache()
    toks = list(range(1, 13))  # 12 tokens: 1 full page + 1 partial
    a, _ = cache.admit(len(toks), toks)
    # fill the pool pages with recognizable data
    kv = cache.pages["kv"]
    for i, p in enumerate(cache._slot_pages[a]):
        kv = kv.at[:, p].set(float(i + 1))
    cache.swap_pages({"kv": kv})

    b = cache.fork(a)
    assert cache._slot_pages[b] == cache._slot_pages[a]
    assert int(cache.lengths[b]) == 12
    assert all(cache.pool.refcounts[p] == 2 for p in cache._slot_pages[a])

    avail = cache.pool.available
    changed = cache.ensure_append_capacity(b)  # next write: pos 12, page 1
    assert changed and cache.stats["cow_copies"] == 1
    assert cache.pool.available == avail - 1  # exactly one page allocated
    pa, pb = cache._slot_pages[a], cache._slot_pages[b]
    assert pb[0] == pa[0] and pb[1] != pa[1]  # full page shared, tail copied
    assert cache.pool.refcounts[pa[0]] == 2
    assert cache.pool.refcounts[pa[1]] == 1 and cache.pool.refcounts[pb[1]] == 1
    # the copy carried the tail page's contents
    kv = np.asarray(cache.pages["kv"])
    np.testing.assert_array_equal(kv[:, pb[1]], kv[:, pa[1]])
    # a's next append sees refcount 1 everywhere: no second copy
    assert not cache.ensure_append_capacity(a)
    assert cache.stats["cow_copies"] == 1

    cache.release(a)
    cache.release(b)
    assert cache.pool.available == cache.num_pages - 1
    assert (cache.pool.refcounts[1:] == 0).all()


def test_cow_append_fails_cleanly_when_pool_exhausted():
    """Pool exhaustion DURING a copy-on-write append: every free page is
    held by refcounted (unfreeable) sharers, so the COW copy has nowhere to
    land. ensure_append_capacity must raise (so the engine can preempt)
    WITHOUT corrupting state: no page leaked, the shared mapping and block
    table untouched, refcounts intact — and the append must succeed after
    pressure drops."""
    cache = _small_cache(num_pages=4)  # pages 1..3 usable
    toks = list(range(1, 13))  # 12 tokens: 1 full page + 1 partial
    a, _ = cache.admit(len(toks), toks)   # takes pages 1, 2
    b = cache.fork(a)                     # maps both COW (refcounts 2)
    (filler,) = cache.pool.alloc(1)       # page 3: pool now empty
    assert cache.pool.available == 0

    before_pages = list(cache._slot_pages[b])
    before_bt = cache.block_tables[b].copy()
    before_rc = cache.pool.refcounts.copy()
    # b's next write lands at position 12 inside shared page 2 -> COW needs
    # a fresh page, but every page is refcounted and unfreeable
    with pytest.raises(RuntimeError, match="exhausted"):
        cache.ensure_append_capacity(b)
    assert cache.stats["cow_copies"] == 0
    assert cache._slot_pages[b] == before_pages      # mapping unchanged
    np.testing.assert_array_equal(cache.block_tables[b], before_bt)
    np.testing.assert_array_equal(cache.pool.refcounts, before_rc)
    assert cache.pool.available == 0                 # nothing leaked

    # releasing unrelated pressure makes the SAME append succeed as a copy
    cache.pool.free([filler])
    assert cache.ensure_append_capacity(b) is True
    assert cache.stats["cow_copies"] == 1
    assert cache._slot_pages[b][1] != cache._slot_pages[a][1]
    cache.release(a)
    cache.release(b)
    assert cache.pool.available == cache.num_pages - 1


def test_cow_exhaustion_growth_page_also_raises():
    """The page-boundary growth branch hits the same exhaustion path: a
    slot at a page boundary with an empty pool raises instead of stealing a
    refcounted page, and the pool stays balanced."""
    cache = _small_cache(num_pages=3, page_size=8)  # pages 1..2 usable
    toks = list(range(1, 9))  # exactly one full page
    a, _ = cache.admit(len(toks), toks)
    b = cache.fork(a)          # page shared at refcount 2
    (filler,) = cache.pool.alloc(1)
    with pytest.raises(RuntimeError, match="exhausted"):
        cache.ensure_append_capacity(a)  # boundary: needs a NEW page
    assert cache.pool.refcounts[cache._slot_pages[a][0]] == 2
    cache.pool.free([filler])
    assert cache.ensure_append_capacity(a) is True   # growth succeeds now
    cache.release(a)
    cache.release(b)
    assert cache.pool.available == cache.num_pages - 1
    assert (cache.pool.refcounts[1:] == 0).all()


def test_engine_preempts_when_cow_append_cannot_allocate(smollm):
    """Engine-level: shared prefix pages make the pool LOOK full of
    unfreeable pages; when a decode append needs a page the scheduler must
    preempt the youngest sequence (whose release drops the shared
    refcounts) instead of crashing, and every request still finishes
    exactly."""
    cfg, model, params = smollm
    # 7 usable pages; two 17-token same-prefix prompts share 2 full pages:
    # 2 shared + 2 private tails + growth quickly exceeds the pool
    eng = ContinuousBatchingEngine(cfg, params, max_len=48, max_slots=3,
                                   page_size=8, num_pages=8,
                                   prefill_chunk=8)
    prefix = list(range(40, 56))  # 2 full pages
    reqs = [Request(f"c{i}", prefix + [60 + i], max_new_tokens=14)
            for i in range(3)]
    out = eng.generate(reqs)
    assert eng.cache.stats["prefix_hits"] >= 1  # sharing actually happened
    assert eng.stats["preemptions"] > 0         # pressure forced eviction
    base = GenerationEngine(cfg, params, max_len=48)
    for r, o in zip(reqs, out):
        exact = base.generate([Request(r.uid, r.prompt, r.max_new_tokens)])[0]
        assert o.tokens == exact.tokens, r.uid
    assert_drained(eng.cache)


def test_prefill_chunk_matches_whole_prefill(smollm):
    """Chunked prefill (2 chunks) reproduces the whole-prompt prefill's
    KV pages and final-position logits."""
    cfg, model, params = smollm
    plen, chunk = 11, 8
    prompt = np.arange(1, plen + 1, dtype=np.int32)
    batch = {"tokens": jnp.asarray(prompt[None])}
    dcache, dlogits = jax.jit(
        lambda p, b, i: model.prefill(p, b, plen, logits_index=i)
    )(params, batch, jnp.asarray(plen - 1, jnp.int32))

    paged = PagedKVCache(
        num_layers=cfg.num_layers, num_kv_heads=cfg.eff_kv_heads,
        head_dim=cfg.head_dim, dtype=jnp.dtype(cfg.dtype),
        max_slots=2, max_context=32, page_size=4,
    )
    slot, _ = paged.admit(context_len=plen)
    row = paged.device_row(slot)
    pages = dict(paged.pages)
    logits = None
    for start in range(0, plen, chunk):
        valid = min(chunk, plen - start)
        toks = np.zeros((chunk,), np.int32)
        toks[:valid] = prompt[start:start + valid]
        pages, logits = model.prefill_chunk(
            params, pages, row, jnp.asarray(toks),
            jnp.asarray(start, jnp.int32), jnp.asarray(valid, jnp.int32),
        )
    paged.swap_pages(pages)
    got_k, got_v = paged.gather_dense(slot)
    np.testing.assert_allclose(
        got_k, np.asarray(dcache["k"][:, 0, :plen]), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        got_v, np.asarray(dcache["v"][:, 0, :plen]), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(dlogits[0]), atol=1e-4, rtol=1e-4
    )


def test_engine_chunked_long_prompt_matches_lockstep(smollm):
    """A multi-chunk prompt through the chunked engine stays exact."""
    cfg, model, params = smollm
    rng = np.random.default_rng(5)
    reqs = [
        Request("long", list(rng.integers(1, cfg.vocab_size, 50)), 8),
        Request("short", list(rng.integers(1, cfg.vocab_size, 5)), 8),
    ]
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   page_size=8, prefill_chunk=16)
    out = eng.generate(reqs)
    assert eng.stats["prefill_chunks"] >= 4  # 50-token prompt = 4 chunks
    base = GenerationEngine(cfg, params, max_len=64)
    for r, o in zip(reqs, out):
        exact = base.generate([Request(r.uid, r.prompt, r.max_new_tokens)])[0]
        assert o.tokens == exact.tokens, r.uid
    assert_drained(eng.cache)


def test_engine_prefix_sharing_reuses_pages_and_stays_exact(smollm):
    """Identical prompts in flight share prefix pages (trie hits recorded)
    and greedy outputs match the no-sharing engine."""
    cfg, model, params = smollm
    rng = np.random.default_rng(9)
    prefix = list(rng.integers(1, cfg.vocab_size, 24))
    reqs = [Request(f"s{i}", prefix + [10 + i], max_new_tokens=6)
            for i in range(4)]
    shared = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=4,
                                      page_size=8, prefill_chunk=16)
    out_shared = shared.generate(reqs)
    assert shared.cache.stats["prefix_hits"] >= 1
    assert shared.cache.stats["prefix_tokens_reused"] >= 16

    plain = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=4,
                                     page_size=8, prefill_chunk=16,
                                     prefix_sharing=False)
    out_plain = plain.generate(
        [Request(r.uid, r.prompt, r.max_new_tokens) for r in reqs]
    )
    assert plain.cache.stats["prefix_hits"] == 0
    for a, b in zip(out_shared, out_plain):
        assert a.tokens == b.tokens, a.uid
    assert_drained(shared.cache)


def test_chunked_prefill_interleaves_with_decode(smollm):
    """While a long prompt prefills chunk-by-chunk, an in-flight decode
    keeps emitting: it must finish BEFORE the long prompt's first token."""
    cfg, model, params = smollm
    eng = ContinuousBatchingEngine(cfg, params, max_len=128, max_slots=2,
                                   page_size=8, prefill_chunk=8)
    short = eng.submit(Request("short", [1, 2, 3], max_new_tokens=6))
    eng.step()  # short: single-chunk prefill + first token
    long_prompt = list(range(1, 81))  # 10 chunks of 8
    long = eng.submit(Request("long", long_prompt, max_new_tokens=2))
    order = []
    while not eng.idle:
        order.extend(ev.uid for ev in eng.step() if ev.kind == "finish")
    assert order == ["short", "long"]
    assert len(short.tokens) == 6
    assert len(long.tokens) == 2
    # decode steps ran while the long prompt was still chunking
    assert eng.stats["prefill_chunks"] >= 10
    assert eng.stats["decode_steps"] >= 5


def test_engine_records_latency_metrics(smollm):
    cfg, model, params = smollm
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                   page_size=8)
    (res,) = eng.generate([Request("t", [1, 2, 3, 4], max_new_tokens=5)])
    assert res.ttft is not None and res.ttft > 0
    assert len(res.itl) == 4  # gaps between the 5 emitted tokens
    assert all(g > 0 for g in res.itl)


def test_engine_admits_from_bus(smollm, tmp_path):
    from repro.core import TopicBus

    cfg, model, params = smollm
    bus = TopicBus(tmp_path)
    for i in range(5):
        bus.publish("requests", {
            "uid": f"b{i}", "prompt": [1 + i, 2, 3], "max_new_tokens": 4,
        })
    eng = ContinuousBatchingEngine(cfg, params, max_len=32, max_slots=2,
                                   page_size=8)
    served: dict[str, list[int]] = {}
    while bus.lag("requests", "g0") > 0 or not eng.idle:
        eng.admit_from_bus(bus, "requests", "g0",
                           max_msgs=eng.cache.free_slot_count)
        for ev in eng.step():
            if ev.kind == "token":  # streamed deltas rebuild the outputs
                served.setdefault(ev.uid, []).append(ev.token)
    assert sorted(served) == [f"b{i}" for i in range(5)]
    assert all(len(t) == 4 for t in served.values())


def test_kernel_path_engine_streams_match_ref_path(smollm):
    """The REAL Pallas kernels (interpret mode on CPU), run end-to-end inside
    the engine — chunked-prefill kernel per chunk, decode kernel per step —
    must produce byte-identical token streams to the XLA reference path.
    Under the forced 4-device CI job the same test exercises the kernels
    per shard inside the executor's ``shard_map``."""
    cfg, model, params = smollm
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, 200, n)) for n in (11, 19, 6)]

    def run(attn_impl):
        eng = ContinuousBatchingEngine(
            cfg, params, max_len=48, max_slots=2, page_size=8,
            prefill_chunk=8, attn_impl=attn_impl,
        )
        handles = [eng.submit(Request(f"k{i}", list(p), max_new_tokens=4))
                   for i, p in enumerate(prompts)]
        while not eng.idle:
            eng.step()
        return [h.result().tokens for h in handles]

    kernel, ref_path = run("pallas_interpret"), run("xla_chunked")
    assert kernel == ref_path, (kernel, ref_path)
    assert all(len(t) == 4 for t in kernel)


def test_fused_step_streams_match_interleaved(smollm):
    """The fused step (one mixed dispatch per engine step) must produce
    byte-identical token streams to the interleaved two-dispatch step, on a
    trace that keeps prefill chunks and decodes overlapping (staggered
    arrivals, mixed greedy/sampled rows) — and the fused engine must have
    actually fused (mixed dispatches recorded). Under the forced 4-device
    CI job the same test exercises the mixed kernel per shard inside the
    executor's ``shard_map``."""
    cfg, model, params = smollm
    rng = np.random.default_rng(13)
    reqs = [
        Request(f"f{i}", list(rng.integers(1, 200, int(rng.integers(6, 30)))),
                max_new_tokens=int(rng.integers(4, 12)),
                temperature=0.0 if i % 2 else 0.9)
        for i in range(6)
    ]

    def run(mode, token_budget=None):
        eng = ContinuousBatchingEngine(
            cfg, params, max_len=64, max_slots=3, page_size=8,
            prefill_chunk=8, step_mode=mode, token_budget=token_budget,
            seed=3,
        )
        pending = [Request(r.uid, list(r.prompt), r.max_new_tokens,
                           temperature=r.temperature) for r in reqs]
        handles = []
        # staggered arrivals: a new request every 2 steps keeps chunks
        # landing while other slots decode — the fused regime
        while pending or not eng.idle:
            if pending:
                handles.append(eng.submit(pending.pop(0)))
            eng.step()
            if pending:
                handles.append(eng.submit(pending.pop(0)))
            eng.step()
        return [h.result().tokens for h in handles], eng

    fused, ef = run("fused")
    inter, ei = run("interleaved")
    assert fused == inter, (fused, inter)
    assert all(t for t in fused)
    assert ef.utilization.fused_dispatches > 0   # the mixed path really ran
    assert ei.utilization.fused_dispatches == 0
    # identical model work either way, in fewer dispatches
    assert ef.utilization.dispatches < ei.utilization.dispatches \
        + ef.stats["prefill_chunks"]

    # a token budget reshapes the schedule (chunks get deferred/trimmed)
    # but never the streams
    budget, _ = run("fused", token_budget=6)
    assert budget == fused
