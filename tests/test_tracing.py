"""Spans and counters inside the serving engines (``serving/metrics.py``).

Each engine step is a ``serving.step`` span split into phases (admit,
plan, pack, dispatch, sync, deliver; tier_prefetch and tier_spill inside
them), kept as per-step records in a bounded ring; each request carries
``submitted`` / ``admitted`` / ``prefill_start`` stamps. Parameterized
over the paged and the slot-state engine: the records fit their steps,
the stamps stay ordered through a preemption, the ring stays bounded
through ``merge``, and a profiler trace holds the same spans nested.
"""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import build_model
from repro.serving import (
    ContinuousBatchingEngine,
    GenerationEngine,
    KVTierManager,
    PagedKVCache,
    Request,
    SSMEngine,
)
from repro.serving import metrics as metrics_mod
from repro.serving.metrics import (
    UtilizationMetrics,
    format_waits,
    wait_percentiles,
)

PHASES = {"admit", "plan", "pack", "dispatch", "sync", "deliver"}


@pytest.fixture(scope="module")
def smollm():
    cfg = reduced(ARCHS["smollm-360m"])
    return cfg, build_model(cfg).init(jax.random.key(0))


@pytest.fixture(scope="module")
def mamba2():
    cfg = reduced(ARCHS["mamba2-1.3b"])
    return cfg, build_model(cfg).init(jax.random.key(0))


@pytest.fixture(params=["paged", "ssm"])
def make_engine(request, smollm, mamba2):
    kind = request.param

    def factory(**kw):
        if kind == "paged":
            return ContinuousBatchingEngine(
                *smollm, max_len=kw.pop("max_len", 64),
                max_slots=kw.pop("slots", 3), page_size=8,
                prefill_chunk=8, **kw)
        return SSMEngine(*mamba2, max_len=kw.pop("max_len", 64),
                         max_slots=kw.pop("slots", 3), prefill_chunk=8, **kw)

    factory.kind = kind
    return factory


def _requests(n, plen=19, new=6):
    return [Request(f"r{i}", [3 + i] + list(range(10, 10 + plen - 1)),
                    max_new_tokens=new) for i in range(n)]


def drain(engine) -> int:
    steps = 0
    while not engine.idle:
        engine.step()
        steps += 1
    return steps


def test_step_records_fit_their_steps(make_engine):
    eng = make_engine()
    for r in _requests(4):
        eng.submit(r)
    steps = drain(eng)
    u = eng.utilization
    recs = list(u.step_records)
    assert len(recs) == steps == u.phase_steps
    seen = set()
    for rec in recs:
        assert rec.start_ns <= rec.end_ns
        assert set(rec.phases) <= PHASES
        assert all(ns >= 0 for ns in rec.phases.values())
        assert sum(rec.phases.values()) <= rec.end_ns - rec.start_ns
        seen |= set(rec.phases)
    assert seen == PHASES
    for a, b in zip(recs, recs[1:]):
        assert a.end_ns <= b.start_ns
    # running totals: the whole steps and each phase's self time
    assert u.phase_ns["step"] == sum(r.end_ns - r.start_ns for r in recs)
    for name in PHASES:
        assert u.phase_ns[name] == sum(r.phases.get(name, 0) for r in recs)
    per_step = u.phase_ms_per_step()
    assert per_step["step"] >= sum(per_step[k] for k in PHASES)
    assert u.format_phases().startswith("step=")


def _check_order(h):
    first = h.arrival + h.ttft
    assert h.submitted <= h.admitted <= h.prefill_start <= first
    res = h.result()
    assert res.queue_wait == pytest.approx(h.admitted - h.submitted)
    assert res.prefill_wait == pytest.approx(h.prefill_start - h.admitted)


def test_lifecycle_stamps_ordered_through_preemption(make_engine):
    if make_engine.kind == "paged":
        # a pool too small for both prompts: the scheduler preempts
        eng = make_engine(max_len=40, slots=2, num_pages=6)
        reqs = [Request(f"p{i}", [100 + i] + list(range(2, 15)),
                        max_new_tokens=10) for i in range(3)]
        handles = [eng.submit(r) for r in reqs]
        firsts = {}
        while not eng.idle:
            eng.step()
            for h in handles:
                if h.admitted is not None:
                    firsts.setdefault(h.uid, (h.admitted, h.prefill_start))
    else:
        eng = make_engine(slots=2)
        handles = [eng.submit(r) for r in _requests(2)]
        while len(handles[1].tokens) < 3:
            eng.step()
        firsts = {h.uid: (h.admitted, h.prefill_start) for h in handles}
        assert eng.preempt_youngest() is not None
        drain(eng)
    assert eng.stats["preemptions"] > 0
    preempted = [h for h in handles if h.preemptions]
    assert preempted
    for h in handles:
        assert h.done and h.tokens
        _check_order(h)
        # re-admission after a preemption keeps the first stamps
        assert (h.admitted, h.prefill_start) == firsts[h.uid]


def test_lockstep_engine_stamps_too(smollm):
    eng = GenerationEngine(*smollm, max_len=64, max_batch=2)
    handles = [eng.submit(r) for r in _requests(3)]
    drain(eng)
    for h in handles:
        _check_order(h)
    # the third request waited for the first batch to retire
    assert handles[2].admitted > handles[1].admitted


def test_step_ring_bounded_through_merge(make_engine, monkeypatch):
    monkeypatch.setattr(metrics_mod, "STEP_RING", 4)
    eng = make_engine()
    eng.utilization = UtilizationMetrics()
    for r in _requests(3):
        eng.submit(r)
    steps = drain(eng)
    assert steps > 4
    u = eng.utilization
    assert len(u.step_records) == 4 and u.phase_steps == steps
    merged = UtilizationMetrics()
    merged.merge(u)
    merged.merge(u)
    assert len(merged.step_records) == 4
    assert merged.phase_steps == 2 * steps
    assert merged.phase_ns["step"] == 2 * u.phase_ns["step"]


def _host_events(logdir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, ln in enumerate(plane.lines):
                for ev in ln.events:
                    if ev.name.startswith("serving."):
                        s = ev.start_ns
                        out.append((ev.name, s, s + ev.duration_ns, i))
    return out


def test_profiler_trace_nests_phases_in_steps(make_engine, tmp_path):
    eng = make_engine()
    for r in _requests(3):
        eng.submit(r)
    for _ in range(2):  # compile outside the trace
        eng.step()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            eng.step()
    evs = _host_events(tmp_path)
    steps = [e for e in evs if e[0] == "serving.step"]
    phases = [e for e in evs if e[0] != "serving.step"]
    assert len(steps) == 3
    assert {e[0] for e in phases} >= {"serving.dispatch", "serving.sync",
                                      "serving.deliver"}
    for name, s, e, line in phases:
        assert any(line == ln and s0 <= s and e <= e0
                   for _, s0, e0, ln in steps), name


def test_tier_spill_and_prefetch_are_phases():
    """The tier copies run as ``serving.tier_spill``/``tier_prefetch``
    phases, and the tier counters still add up their seconds."""
    tiers = KVTierManager(host_pages=8)
    cache = PagedKVCache(num_layers=2, num_kv_heads=2, head_dim=4,
                         dtype=jnp.float32, max_slots=2, max_context=64,
                         page_size=8, num_pages=8, tiers=tiers)
    toks = list(range(50, 75))
    u = UtilizationMetrics()
    with u.step():
        slot, _ = cache.admit(len(toks), toks)
        cache.register_prefix(slot, toks, len(toks))
        cache.release(slot)
        slot2, _ = cache.admit(41, list(range(300, 341)))  # spills
        cache.release(slot2)
        assert not cache.can_admit(len(toks), toks)  # prefetches
    (rec,) = u.step_records
    assert tiers.counters["spilled_pages"] == 3
    assert tiers.counters["prefetched_pages"] == 3
    assert rec.phases["tier_spill"] > 0 and rec.phases["tier_prefetch"] > 0
    assert tiers.counters["spill_s"] == pytest.approx(
        rec.phases["tier_spill"] / 1e9)
    assert tiers.counters["prefetch_s"] == pytest.approx(
        rec.phases["tier_prefetch"] / 1e9)


def test_phase_outside_a_step_is_only_timed():
    with metrics_mod.phase("pack") as span:
        np.zeros(1000).sum()
    assert span.ns > 0
    u = UtilizationMetrics()
    assert u.phase_ms_per_step() is None
    assert u.format_phases() == "no_step_data"


def test_wait_percentiles_and_format():
    class R:
        def __init__(self, q, p):
            self.queue_wait, self.prefill_wait = q, p

    rs = [R(0.001 * i, 0.01 * i) for i in range(1, 101)] + [R(None, None)]
    p = wait_percentiles(rs)
    assert p["queue_wait_ms"][0] == pytest.approx(50.5)
    assert p["prefill_wait_ms"][1] == pytest.approx(950.5)
    assert format_waits(rs).startswith("queue_wait_ms_p50=50.5/p95=95.")
    assert format_waits([R(None, None)]) == "no_wait_data"
