"""Scheduler/executor split: host-policy units + sharded-executor parity.

The scheduler tests drive pure host-side decisions (placement, chunk
ordering, prefix deferral, preemption, decode-batch masking) against a
:class:`PagedKVCache` without dispatching a single model step — the point
of the split. The executor tests assert the tensor-parallel mesh contract:
pages sharded along the kv-head dim, embedding replicated, and the sharded
engine producing byte-identical token streams to a forced 1-device mesh.

Sharding-specific tests need >= 2 local devices and skip otherwise; CI runs
this file (with the rest of the serving tests) under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so the sharded path
is exercised on every PR without TPU hardware.
"""

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import build_model
from repro.serving import (
    ContinuousBatchingEngine,
    PagedKVCache,
    Request,
    RequestHandle,
    SamplingParams,
)
from repro.serving.executor import pick_tp, serving_mesh_scope
from repro.serving.kv_cache import NULL_PAGE
from repro.serving.scheduler import Scheduler
from repro.launch.mesh import make_serving_mesh

import jax.numpy as jnp


# ---------------------------------------------------------------------------
# scheduler: pure host-side policy
# ---------------------------------------------------------------------------


def _cache(**kw):
    args = dict(num_layers=1, num_kv_heads=1, head_dim=4, dtype=jnp.float32,
                max_slots=3, max_context=64, page_size=8)
    args.update(kw)
    return PagedKVCache(**args)


def _sched(cache, **kw):
    args = dict(prefill_chunk=8, chunked=True, prefix_sharing=True)
    args.update(kw)
    return Scheduler(cache, **args)


def _req(uid, prompt, **kw):
    r = Request(uid, prompt, **kw)
    return r, RequestHandle(r)


def test_scheduler_place_and_chunk_ordering():
    """Chunked placement starts in the prefill phase; next_prefill always
    advances the OLDEST prefill; completion flips the slot decodable."""
    sched = _sched(_cache())
    r0, h0 = _req("r0", list(range(1, 13)))   # 12 tokens: 2 chunks
    r1, h1 = _req("r1", list(range(20, 25)))  # 5 tokens: 1 chunk
    s0, q0, cached = sched.place(r0, h0)
    assert q0.phase == "prefill" and cached == 0
    s1, q1, _ = sched.place(r1, h1)
    assert not sched.has_decodable()

    work = sched.next_prefill()
    assert work.slot == s0 and work.start == 0 and work.valid == 8
    assert list(work.tokens[:8]) == r0.prompt[:8]
    assert sched.complete_chunk(work) is False   # 4 tokens left
    work = sched.next_prefill()
    assert work.slot == s0 and work.start == 8 and work.valid == 4
    assert (work.tokens[4:] == 0).all()          # padded fixed-size chunk
    assert sched.complete_chunk(work) is True
    sched.begin_decode(s0)
    q0.tokens.append(7)
    assert sched.has_decodable()
    # r1 becomes the oldest remaining prefill
    assert sched.next_prefill().slot == s1


def test_scheduler_decode_batch_masks_prefilling_slots():
    """build_decode_inputs: decoding slots carry their sampling state;
    prefilling/idle slots are masked to the null page / length 0 so the
    executor's scatter lands in the sink."""
    cache = _cache()
    sched = _sched(cache)
    r0, h0 = _req("d", [1, 2, 3, 4], sampling=SamplingParams(
        temperature=0.9, top_k=5, top_p=0.8, max_new_tokens=4, seed=11))
    h0.seed = 11
    s0, q0, _ = sched.place(r0, h0)
    sched.complete_chunk(sched.next_prefill())
    sched.begin_decode(s0)
    q0.tokens.append(42)
    r1, h1 = _req("p", list(range(1, 12)))
    s1, _, _ = sched.place(r1, h1)           # still prefilling

    inputs = sched.build_decode_inputs()
    assert sched.dirty is False
    assert inputs.greedy_only is False       # sampled request in flight
    assert inputs.active[s0] == 1 and inputs.tokens[s0, 0] == 42
    assert inputs.temps[s0] == np.float32(0.9)
    assert inputs.seeds[s0] == 11 and inputs.idx[s0] == 1
    assert inputs.active[s1] == 0
    assert (inputs.block_tables[s1] == NULL_PAGE).all()
    assert inputs.lengths[s1] == 0
    # the cache's own table for the prefilling slot is NOT nulled
    assert cache.block_tables[s1, 0] != NULL_PAGE


def test_scheduler_prefix_deferral_until_inflight_publishes():
    """Admission defers while an in-flight prefill is about to publish a
    longer prefix than the index currently holds — then admits with the
    shared pages mapped."""
    cache = _cache()
    sched = _sched(cache)
    prompt = list(range(1, 25))              # 3 full pages, 2 shareable
    r0, h0 = _req("a", prompt)
    sched.place(r0, h0)
    r1, h1 = _req("b", list(prompt))
    assert sched.can_place(r1) is False      # 16 shareable tokens pending
    sched.complete_chunk(sched.next_prefill())   # publishes page 0
    assert sched.can_place(r1) is False      # still one more page coming
    sched.complete_chunk(sched.next_prefill())   # publishes page 1
    assert sched.can_place(r1) is True
    _, _, cached = sched.place(r1, h1)
    assert cached == 16
    assert cache.stats["prefix_hits"] == 1


def test_scheduler_preempts_youngest_for_capacity():
    """ensure_decode_capacity evicts the youngest sequence (releasing its
    pages) until every decoding slot can take its next write."""
    cache = _cache(num_pages=5, max_slots=3)  # 4 usable pages
    sched = _sched(cache, prefix_sharing=False)
    seqs = []
    for i in range(2):
        r, h = _req(f"r{i}", [10 * i + j for j in range(15)])  # 2 pages each
        slot, seq, _ = sched.place(r, h)
        seq.prefill_pos = 15
        sched.begin_decode(slot)
        seq.tokens.append(1)
        seqs.append(seq)
    # both slots at 15/16 within page 2; appending past 16 needs new pages:
    # only 0 free -> the youngest must go
    cache.lengths[:] = [16, 16, 0]
    preempted = sched.ensure_decode_capacity()
    assert [s.request.uid for s in preempted] == ["r1"]
    assert sched.has_decodable()             # r0 kept and can now grow
    assert cache.pool.available >= 0 and sched.dirty


def test_scheduler_gauges():
    sched = _sched(_cache())
    r, h = _req("g", [1, 2, 3])
    slot, seq, _ = sched.place(r, h)
    assert sched.occupancy() == (0, 3)
    sched.begin_decode(slot)
    assert sched.occupancy() == (1, 3)
    used, total = sched.page_utilization()
    assert used == 1 and total == sched.cache.num_pages - 1


# ---------------------------------------------------------------------------
# executor: mesh selection + sharding contract
# ---------------------------------------------------------------------------


def test_pick_tp_respects_divisibility():
    cfg = reduced(ARCHS["smollm-360m"])      # kv=2, heads=4, ff=128, tied
    assert pick_tp(cfg, 1) == 1
    assert pick_tp(cfg, 2) == 2
    assert pick_tp(cfg, 4) == 2              # kv_heads=2 caps the degree
    assert pick_tp(cfg, 3) == 2
    untied = reduced(ARCHS["llama3-8b"])     # untied: padded vocab counts
    assert pick_tp(untied, 2) == 2


@pytest.fixture(scope="module")
def smollm():
    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, params


def _trace(cfg, n=5):
    rng = np.random.default_rng(3)
    reqs = [
        Request(f"t{i}",
                list(rng.integers(1, cfg.vocab_size, rng.integers(3, 40))),
                max_new_tokens=int(rng.integers(2, 9)))
        for i in range(n)
    ]
    reqs.append(Request("hot", [5, 6, 7], sampling=SamplingParams(
        temperature=1.0, top_k=20, top_p=0.9, seed=13, max_new_tokens=6)))
    return reqs


def test_executor_single_device_mesh_runs_everything(smollm):
    """The 1-device mesh is the same shard_map code path with the
    collectives compiled away — exactness vs lockstep is asserted by the
    conformance suite; here we pin the wiring."""
    cfg, params = smollm
    with serving_mesh_scope(make_serving_mesh(1)):
        eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=3,
                                       page_size=8)
    assert eng.executor.tp == 1
    assert eng.executor.mesh.axis_names == ("model",)
    out = eng.generate(_trace(cfg, n=3))
    assert all(len(o.tokens) == r.max_new_tokens
               for r, o in zip(_trace(cfg, n=3), out))
    # at drain every page is free or parked-for-reuse (tiers are on by
    # default with prefix sharing); null page 0 stays reserved
    assert (eng.cache.pool.available + eng.cache.parked_count
            == eng.cache.num_pages - 1)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs a multi-device host (CI forces 4 CPU "
                           "devices via XLA_FLAGS)")
def test_pages_and_params_sharded_over_model_axis(smollm):
    """The page pool shards along its lanes, whole kv heads per shard (same
    pages on every shard), attention weights shard along their head dims,
    and the token embedding stays replicated."""
    cfg, params = smollm
    eng = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=3,
                                   page_size=8)
    ex = eng.executor
    assert ex.tp >= 2
    kvh_local = cfg.eff_kv_heads // ex.tp
    for shard in eng.cache.pages["kv"].addressable_shards:
        # lane dim sharded: each shard holds its heads' K|V lane groups
        assert shard.data.shape[3] == kvh_local * 2 * cfg.head_dim
        assert shard.data.shape[1] == eng.cache.num_pages  # pages NOT
    wq = ex.params["layers"]["attn"]["wq"]
    h_local = cfg.eff_heads // ex.tp
    assert {s.data.shape[2] for s in wq.addressable_shards} == {h_local}
    emb = ex.params["embed"]
    assert all(s.data.shape == emb.shape for s in emb.addressable_shards)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs a multi-device host")
def test_sharded_engine_matches_single_device_tokens(smollm):
    """Token streams (greedy AND seeded-sampled) are byte-identical between
    the auto-sharded mesh and a forced 1-device mesh."""
    cfg, params = smollm
    sharded = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=3,
                                       page_size=8)
    assert sharded.executor.tp >= 2
    out_s = sharded.generate(_trace(cfg))
    with serving_mesh_scope(make_serving_mesh(1)):
        single = ContinuousBatchingEngine(cfg, params, max_len=64,
                                          max_slots=3, page_size=8)
    out_1 = single.generate(_trace(cfg))
    for a, b in zip(out_s, out_1):
        assert a.tokens == b.tokens, a.uid
    assert sharded.cache.pool.available == sharded.cache.num_pages - 1


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs a multi-device host")
def test_untied_vocab_sharded_logits_gather(smollm_unused=None):
    """Untied embeddings shard the unembed columns; the logits all-gather
    must reassemble the full distribution — sharded tokens equal the
    1-device mesh's, including the whole-prompt (legacy) prefill path."""
    cfg = reduced(ARCHS["llama3-8b"])
    assert not cfg.tie_embeddings
    params = build_model(cfg).init(jax.random.key(1))
    sharded = ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                       page_size=8, prefill_chunk=None)
    assert sharded.executor.vocab_sharded
    reqs = [Request("u0", [1, 2, 3, 4], max_new_tokens=6),
            Request("u1", [9, 8, 7], max_new_tokens=4)]
    out_s = sharded.generate(reqs)
    with serving_mesh_scope(make_serving_mesh(1)):
        single = ContinuousBatchingEngine(cfg, params, max_len=64,
                                          max_slots=2, page_size=8,
                                          prefill_chunk=None)
    out_1 = single.generate([Request("u0", [1, 2, 3, 4], max_new_tokens=6),
                             Request("u1", [9, 8, 7], max_new_tokens=4)])
    for a, b in zip(out_s, out_1):
        assert a.tokens == b.tokens, a.uid


def test_mesh_size_that_does_not_divide_heads_is_rejected(smollm):
    cfg, params = smollm
    if jax.device_count() < 3:
        pytest.skip("needs >= 3 devices to build an indivisible mesh")
    with serving_mesh_scope(make_serving_mesh(3)):  # kv_heads=2 % 3 != 0
        with pytest.raises(ValueError, match="does not divide"):
            ContinuousBatchingEngine(cfg, params, max_len=64, max_slots=2,
                                     page_size=8)
