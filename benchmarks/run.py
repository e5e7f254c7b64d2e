"""Benchmark harness — one function per quantified paper claim (DESIGN.md §5).

The paper itself has no tables (zero quantitative evaluation), so each
benchmark quantifies one of its qualitative claims C1..C6. Prints
``name,us_per_call,derived`` CSV rows, plus kernel and step benches.

The serving benches additionally emit ``experiments/BENCH_serving.json`` —
machine-readable tok/s + TTFT/ITL p50/p90/p99 + trace config per engine —
so the serving perf trajectory is diffable across PRs instead of living
only in docs prose.

Run: PYTHONPATH=src python -m benchmarks.run [--quick]
"""

from __future__ import annotations

import argparse
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

ROWS: list[tuple[str, float, str]] = []
SERVING: dict = {}  # machine-readable serving results -> BENCH_serving.json


def row(name: str, us: float, derived: str):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")


def serving_entry(section: str, name: str, *, tok_per_s: float,
                  results=None, **extra) -> None:
    """Record one serving measurement for ``BENCH_serving.json``:
    throughput, latency percentiles (when the engine reports them) and any
    run metadata the caller wants tracked."""
    from repro.serving import latency_percentiles

    entry: dict = {"tok_per_s": round(tok_per_s, 1), **extra}
    p = latency_percentiles(results) if results else None
    if p is not None:
        for key in ("ttft_ms", "itl_ms"):
            entry[key] = {
                q: round(v, 2) for q, v in zip(("p50", "p90", "p99"), p[key])
            }
        entry["itl_ms_max"] = round(p["itl_ms_max"], 2)
    SERVING.setdefault(section, {}).setdefault("engines", {})[name] = entry


def timeit(fn, n: int, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us


# ---------------------------------------------------------------------------
def bench_split(quick: bool):
    """C1: notebook -> DAG -> steps translation throughput."""
    from repro.core import Notebook, split_pipeline

    n_cells = 40 if quick else 120
    srcs = ["v0 = 1"] + [f"v{i} = v{i-1} + {i}" for i in range(1, n_cells)]
    for i in range(4, n_cells, 5):
        srcs[i] = "# %%pipe\n" + srcs[i]
    nb = Notebook.from_sources(srcs)
    us = timeit(lambda: split_pipeline(nb), 10)
    g = split_pipeline(nb)
    row("split_notebook", us, f"cells={n_cells};steps={len(g.steps)};cells_per_s={n_cells/us*1e6:.0f}")


def bench_bus(quick: bool):
    """C5: topic bus producer/consumer throughput."""
    from repro.core import TopicBus

    n = 500 if quick else 3000
    d = tempfile.mkdtemp()
    try:
        bus = TopicBus(d)
        t0 = time.perf_counter()
        for i in range(n):
            bus.publish("t", {"i": i, "payload": "x" * 64})
        pub_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        msgs = bus.read("t")
        con_s = time.perf_counter() - t0
        assert len(msgs) == n
        row("bus_publish", pub_s / n * 1e6, f"msgs_per_s={n/pub_s:.0f}")
        row("bus_consume", con_s / n * 1e6, f"msgs_per_s={n/con_s:.0f}")
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_storage(quick: bool):
    """C4: artifact store put/get bandwidth, both tiers."""
    from repro.core import ArtifactStore

    size = 1 << 20 if quick else 1 << 24  # 1MB / 16MB
    blob = np.random.default_rng(0).bytes(size)
    d = tempfile.mkdtemp()
    try:
        store = ArtifactStore(d)
        for tier in ("shared", "node"):
            t0 = time.perf_counter()
            ref = store.put(blob, tier=tier)
            put_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            store.get(ref)
            get_s = time.perf_counter() - t0
            row(f"store_put_{tier}", put_s * 1e6, f"MBps={size/put_s/1e6:.0f}")
            row(f"store_get_{tier}", get_s * 1e6, f"MBps={size/get_s/1e6:.0f}")
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_ckpt(quick: bool):
    """C6: checkpoint save/restore bandwidth + elastic reshard."""
    import jax
    import jax.numpy as jnp

    from repro.checkpoint import CheckpointManager

    n = 1 << 20 if quick else 1 << 22
    state = {"params": {f"w{i}": jnp.arange(n // 4, dtype=jnp.float32) for i in range(4)}}
    nbytes = sum(x.size * 4 for x in jax.tree.leaves(state))
    d = tempfile.mkdtemp()
    try:
        ck = CheckpointManager(d)
        t0 = time.perf_counter()
        ck.save(1, state)
        save_s = time.perf_counter() - t0
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        t0 = time.perf_counter()
        ck.restore(like)
        rest_s = time.perf_counter() - t0
        row("ckpt_save", save_s * 1e6, f"MBps={nbytes/save_s/1e6:.0f}")
        row("ckpt_restore", rest_s * 1e6, f"MBps={nbytes/rest_s/1e6:.0f}")
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_step(quick: bool):
    """Train + decode step latency on a reduced config (real execution)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import ARCHS, reduced
    from repro.models import build_model
    from repro.models.api import make_batch
    from repro.configs.base import ShapeConfig
    from repro.train import AdamWConfig, init_train_state, make_train_step

    cfg = reduced(ARCHS["llama3-8b"])
    model = build_model(cfg)
    opt = AdamWConfig(moment_dtype="float32")
    state = init_train_state(model, jax.random.key(0), opt)
    shape = ShapeConfig("b", seq_len=128, global_batch=4, kind="train")
    batch = make_batch(cfg, shape)
    step = jax.jit(make_train_step(model, opt, ga=1))
    n = 3 if quick else 10
    us = timeit(lambda: jax.block_until_ready(step(state, batch)[1]["loss"]), n)
    tok = shape.tokens
    row("train_step_reduced", us, f"tokens_per_s={tok/us*1e6:.0f}")

    cache, logits = jax.jit(lambda p, b: model.prefill(p, b, 160))(state["params"], batch)
    dec = jax.jit(model.decode_step)
    toks = jnp.ones((4, 1), jnp.int32)
    us = timeit(lambda: jax.block_until_ready(dec(state["params"], cache, toks)[1]), n)
    row("decode_step_reduced", us, f"tok_per_s={4/us*1e6:.0f}")


def _latency_summary(results) -> str:
    from repro.serving import format_latency

    return format_latency(results)


def _fresh(reqs):
    """Fresh Request copies so repeated runs stay fully independent."""
    from repro.serving import Request

    return [Request(r.uid, list(r.prompt), r.max_new_tokens, r.temperature)
            for r in reqs]


def _drain(engine, reqs):
    """Drive one trace through the raw protocol (submit + step): the bench
    deliberately measures the loop production callers run, NOT the
    deprecated ``engine.generate`` wrapper — if the wrapper and the
    protocol ever diverge in cost, this catches it."""
    handles = [engine.submit(r) for r in reqs]
    while not engine.idle:
        engine.step()
    return [h.result() for h in handles]


def _best_of(engines: dict, one_run, rounds: int) -> dict:
    """Alternated best-of-``rounds``: every engine runs once per round in a
    fixed rotation, so ambient noise (GC, thermal, page cache) lands on all
    contenders evenly instead of biasing whichever ran last."""
    best: dict = {}
    for _ in range(rounds):
        for name, engine in engines.items():
            s, out = one_run(engine)
            if name not in best or s < best[name][0]:
                best[name] = (s, out)
    return best


def bench_serving(quick: bool):
    """Continuous batching vs lockstep on a mixed-length trace (tokens/sec).

    Trace: prompts 8-128 tokens, max_new 4-64 — the regime where lockstep
    collapses (every batch pads to the longest prompt and decodes for the
    slowest request). The paged engine is measured in BOTH step modes —
    "fused" (one mixed dispatch per step, the default) and "interleaved"
    (the pre-fusion two-dispatch step) — as an alternated best-of-3 A/B,
    all engines warmed on the trace first so the comparison is
    steady-state, not compile time. The paged rows also report TTFT /
    inter-token latency percentiles (requests carry arrival timestamps
    through the engine) and the fused row its dispatch composition.
    """
    import jax

    from repro.configs import ARCHS, reduced
    from repro.launch.mesh import describe_mesh
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine, GenerationEngine, Request
    from repro.serving.metrics import UtilizationMetrics

    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(0)
    n = 12 if quick else 32
    trace = [
        Request(
            f"r{i}",
            list(rng.integers(1, cfg.vocab_size, rng.integers(8, 129))),
            max_new_tokens=int(rng.integers(4, 65)),
        )
        for i in range(n)
    ]
    useful = sum(r.max_new_tokens for r in trace)
    max_len = 128 + 64

    slots = 8
    # every engine is driven through the SAME protocol loop (_drain); the
    # lockstep engine chunks the trace into max_batch micro-batches itself.
    # the honest baseline runs at the SAME concurrency as the paged engine;
    # the small-batch row shows how lockstep degrades as padding/straggler
    # waste grows with batch width
    engines = {
        f"lockstep_b{slots//2}": GenerationEngine(
            cfg, params, max_len=max_len, max_batch=slots // 2),
        f"lockstep_b{slots}": GenerationEngine(
            cfg, params, max_len=max_len, max_batch=slots),
        # page_size=64 keeps the CPU decode gather coarse (measurably
        # cheaper per step than 16 here); the fused row adds the Sarathi
        # token budget so a chunk can never blow a step past ~3x the
        # decode-only cost — that budget is what buys the ITL tail
        "paged": ContinuousBatchingEngine(
            cfg, params, max_len=max_len, max_slots=slots, page_size=64,
            step_mode="fused", token_budget=24),
        "paged_interleaved": ContinuousBatchingEngine(
            cfg, params, max_len=max_len, max_slots=slots, page_size=64,
            step_mode="interleaved"),
    }

    def one_run(engine):
        engine.utilization = UtilizationMetrics()  # gauge this run only
        t0 = time.perf_counter()
        out = _drain(engine, _fresh(trace))
        return time.perf_counter() - t0, out

    for engine in engines.values():
        _drain(engine, _fresh(trace))  # warm: compile each path
    rounds = 2 if quick else 3
    best = _best_of(engines, one_run, rounds)
    lock_small_s, lock_small_res = best[f"lockstep_b{slots//2}"]
    lock_s, lock_res = best[f"lockstep_b{slots}"]
    paged_s, results = best["paged"]
    inter_s, inter_res = best["paged_interleaved"]

    row(f"serve_lockstep_b{slots//2}", lock_small_s * 1e6,
        f"tok_per_s={useful/lock_small_s:.1f}")
    row(f"serve_lockstep_b{slots}", lock_s * 1e6, f"tok_per_s={useful/lock_s:.1f}")
    row("serve_paged", paged_s * 1e6,
        f"tok_per_s={useful/paged_s:.1f};speedup={lock_s/paged_s:.2f}x")
    row("serve_paged_latency", paged_s * 1e6, _latency_summary(results))
    row("serve_paged_interleaved", inter_s * 1e6,
        f"tok_per_s={useful/inter_s:.1f};"
        f"fused_speedup={inter_s/paged_s:.2f}x;{_latency_summary(inter_res)}")

    SERVING["bench_serving"] = {"config": {
        "arch": cfg.name, "requests": n, "prompt_len": [8, 128],
        "max_new": [4, 64], "slots": slots, "max_len": max_len,
        "useful_tokens": useful, "best_of": rounds,
        "mesh": describe_mesh(engines["paged"].executor.mesh),
    }}
    serving_entry("bench_serving", f"lockstep_b{slots//2}",
                  tok_per_s=useful / lock_small_s, results=lock_small_res)
    serving_entry("bench_serving", f"lockstep_b{slots}",
                  tok_per_s=useful / lock_s, results=lock_res)
    serving_entry("bench_serving", "paged", tok_per_s=useful / paged_s,
                  results=results,
                  step_mode="fused",
                  speedup_vs_lockstep=round(lock_s / paged_s, 2),
                  utilization=engines["paged"].utilization.summary())
    serving_entry("bench_serving", "paged_interleaved",
                  tok_per_s=useful / inter_s, results=inter_res,
                  step_mode="interleaved",
                  fused_speedup=round(inter_s / paged_s, 2),
                  utilization=engines["paged_interleaved"].utilization.summary())


def bench_serving_low_load(quick: bool):
    """Low-load decode tails: 2-4 concurrent requests, long decodes,
    staggered arrivals — the regime where the fused step's win is purest.

    Under low concurrency most steps are steady-state decode with an
    occasional prefill chunk from a newly-arrived request. The interleaved
    step pays TWO device dispatches whenever a chunk is pending (chunk,
    then decode), stalling every in-flight decode by a full dispatch; the
    fused step folds the chunk into the decode dispatch, so arrivals stop
    showing up as ITL tail spikes for the requests already decoding.
    Arrivals are staggered by engine step count (deterministic, not
    wall-clock) so both modes see the identical workload; alternated
    best-of-3, ITL percentiles are the headline numbers.
    """
    import jax

    from repro.configs import ARCHS, reduced
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine, Request
    from repro.serving.metrics import UtilizationMetrics

    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(4)
    n = 4 if quick else 12
    gap = 8 if quick else 24  # steps between arrivals -> ~2-4 in flight
    trace = [
        Request(
            f"l{i}",
            list(rng.integers(1, cfg.vocab_size, rng.integers(16, 49))),
            max_new_tokens=int(rng.integers(24, 33)) if quick
            else int(rng.integers(64, 97)),
        )
        for i in range(n)
    ]
    useful = sum(r.max_new_tokens for r in trace)
    max_len = 48 + 96

    def make(mode):
        return ContinuousBatchingEngine(
            cfg, params, max_len=max_len, max_slots=4, page_size=16,
            prefill_chunk=16, step_mode=mode)

    def one_run(engine):
        engine.utilization = UtilizationMetrics()
        pending = _fresh(trace)
        handles = []
        step_i = 0
        t0 = time.perf_counter()
        while pending or not engine.idle:
            while pending and step_i >= gap * len(handles):
                handles.append(engine.submit(pending.pop(0)))
            engine.step()
            step_i += 1
        return time.perf_counter() - t0, [h.result() for h in handles]

    engines = {"fused": make("fused"), "interleaved": make("interleaved")}
    for engine in engines.values():
        one_run(engine)  # warm: compile each path
    rounds = 1 if quick else 3
    best = _best_of(engines, one_run, rounds)
    fused_s, fused_res = best["fused"]
    inter_s, inter_res = best["interleaved"]

    row("serve_lowload_fused", fused_s * 1e6,
        f"tok_per_s={useful/fused_s:.1f};{_latency_summary(fused_res)}")
    row("serve_lowload_interleaved", inter_s * 1e6,
        f"tok_per_s={useful/inter_s:.1f};fused_speedup={inter_s/fused_s:.2f}x;"
        f"{_latency_summary(inter_res)}")

    SERVING["bench_serving_low_load"] = {"config": {
        "arch": cfg.name, "requests": n, "prompt_len": [16, 48],
        "max_new": [24, 32] if quick else [64, 96], "slots": 4,
        "prefill_chunk": 16, "arrival_gap_steps": gap, "max_len": max_len,
        "best_of": rounds,
    }}
    serving_entry("bench_serving_low_load", "fused",
                  tok_per_s=useful / fused_s, results=fused_res,
                  step_mode="fused",
                  utilization=engines["fused"].utilization.summary())
    serving_entry("bench_serving_low_load", "interleaved",
                  tok_per_s=useful / inter_s, results=inter_res,
                  step_mode="interleaved",
                  fused_speedup=round(inter_s / fused_s, 2),
                  utilization=engines["interleaved"].utilization.summary())


def bench_serving_speculative(quick: bool):
    """Speculative decoding on the greedy low-batch decode-bound trace —
    the regime where the serial one-token-per-dispatch chain is the whole
    cost and speculation's k-tokens-per-dispatch verify pays off directly.

    Two workload arms, because acceptance rate is workload-dependent and
    the honest bench shows both ends:

    * ``loop`` — a checkpoint whose greedy rollout degenerates into a
      short cycle (residual branches zeroed, so the logits depend only on
      the last token: every rollout must enter a cycle over the token
      map). Random-init reduced models emit near-uniform pseudo-random
      streams — the WORST case for prompt-lookup — while real greedy
      decoding is famously repetition-prone; this arm models the
      repetitive regime where n-gram lookup actually lands. Headline:
      spec-on vs spec-off tok/s, alternated best-of-3.
    * ``random`` — plain random init, acceptance near zero: bounds the
      overhead speculation costs when every draft is rejected.

    Plus an acceptance-rate sweep over k ∈ {2, 4, 8} on the loop arm
    (single runs; acceptance comes from the utilization counters, not
    wall-clock). Streams are byte-identical spec-on vs spec-off by
    construction — asserted here on every run, not just in the tests."""
    import jax

    from repro.configs import ARCHS, reduced
    from repro.launch.mesh import describe_mesh
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine, Request
    from repro.serving.metrics import UtilizationMetrics

    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    # loop-degenerate checkpoint: zero the residual-branch output
    # projections so each block is the identity and greedy sampling is a
    # fixed map last-token -> next-token (attention/MLP FLOPs still run —
    # only the CONTENT degenerates, the dispatch cost does not)
    loop_params = dict(params)
    layers = {k: dict(v) if isinstance(v, dict) else v
              for k, v in params["layers"].items()}
    layers["attn"] = dict(params["layers"]["attn"])
    layers["attn"]["wo"] = layers["attn"]["wo"] * 0.0
    layers["mlp"] = dict(params["layers"]["mlp"])
    layers["mlp"]["w_down"] = layers["mlp"]["w_down"] * 0.0
    loop_params["layers"] = layers

    rng = np.random.default_rng(11)
    n = 3 if quick else 6
    gap = 8 if quick else 16  # steps between arrivals -> ~2-3 in flight
    trace = [
        Request(
            f"s{i}",
            list(rng.integers(1, cfg.vocab_size, rng.integers(16, 33))),
            max_new_tokens=int(rng.integers(24, 33)) if quick
            else int(rng.integers(64, 97)),
        )
        for i in range(n)
    ]
    useful = sum(r.max_new_tokens for r in trace)
    max_len = 32 + 96

    def make(p, spec, k=8):
        kw = {} if spec == "off" else {"speculative": spec, "spec_k": k}
        return ContinuousBatchingEngine(
            cfg, p, max_len=max_len, max_slots=4, page_size=16,
            prefill_chunk=16, **kw)

    def one_run(engine):
        engine.utilization = UtilizationMetrics()
        pending = _fresh(trace)
        handles = []
        step_i = 0
        t0 = time.perf_counter()
        while pending or not engine.idle:
            while pending and step_i >= gap * len(handles):
                handles.append(engine.submit(pending.pop(0)))
            engine.step()
            step_i += 1
        return time.perf_counter() - t0, [h.result() for h in handles]

    def streams(results):
        return {r.uid: tuple(r.tokens) for r in results}

    engines = {"off": make(loop_params, "off"),
               "ngram": make(loop_params, "ngram")}
    warm = {name: one_run(engine)[1] for name, engine in engines.items()}
    assert streams(warm["ngram"]) == streams(warm["off"]), \
        "speculative streams diverged from spec-off"
    rounds = 1 if quick else 3
    best = _best_of(engines, one_run, rounds)
    off_s, off_res = best["off"]
    spec_s, spec_res = best["ngram"]
    assert streams(spec_res) == streams(off_res)
    spec_util = engines["ngram"].utilization.summary()

    row("serve_spec_off", off_s * 1e6, f"tok_per_s={useful/off_s:.1f}")
    row("serve_spec_ngram", spec_s * 1e6,
        f"tok_per_s={useful/spec_s:.1f};spec_speedup={off_s/spec_s:.2f}x;"
        f"accept={spec_util['speculation']['acceptance_rate']:.0%}")

    # acceptance-rate sweep: how tokens/bundle scales with draft depth
    sweep = {}
    for k in (2, 4, 8):
        e = make(loop_params, "ngram", k=k)
        one_run(e)  # warm: each k compiles its own verify width
        t_k, res_k = one_run(e)
        assert streams(res_k) == streams(warm["off"])
        sp = e.utilization.summary()["speculation"]
        sweep[f"k{k}"] = {
            "tok_per_s": useful / t_k,
            "acceptance_rate": round(sp["acceptance_rate"], 3),
            "tokens_per_bundle": round(sp["tokens_per_bundle"], 2),
            "bundles": sp["bundles"],
        }
        row(f"serve_spec_sweep_k{k}", t_k * 1e6,
            f"tok_per_s={useful/t_k:.1f};"
            f"accept={sp['acceptance_rate']:.0%};"
            f"tok_per_bundle={sp['tokens_per_bundle']:.2f}")

    # adversarial arm: pseudo-random streams, every draft rejected —
    # bounds the overhead of speculating and never landing
    rand = {"off": make(params, "off"), "ngram": make(params, "ngram")}
    for engine in rand.values():
        one_run(engine)
    rand_best = _best_of(rand, one_run, 1)
    roff_s, roff_res = rand_best["off"]
    rspec_s, rspec_res = rand_best["ngram"]
    assert streams(rspec_res) == streams(roff_res)
    rand_util = rand["ngram"].utilization.summary()
    rand_accept = (rand_util.get("speculation") or {}).get(
        "acceptance_rate", 0.0)
    row("serve_spec_random", rspec_s * 1e6,
        f"tok_per_s={useful/rspec_s:.1f};"
        f"vs_off={roff_s/rspec_s:.2f}x;accept={rand_accept:.0%}")

    SERVING["bench_serving_speculative"] = {"config": {
        "arch": cfg.name, "requests": n, "prompt_len": [16, 32],
        "max_new": [24, 32] if quick else [64, 96], "slots": 4,
        "prefill_chunk": 16, "arrival_gap_steps": gap, "max_len": max_len,
        "spec_k": 8, "best_of": rounds, "greedy": True,
        "mesh": describe_mesh(engines["off"].executor.mesh),
    }}
    serving_entry("bench_serving_speculative", "loop_off",
                  tok_per_s=useful / off_s, results=off_res)
    serving_entry("bench_serving_speculative", "loop_ngram",
                  tok_per_s=useful / spec_s, results=spec_res,
                  spec_speedup=round(off_s / spec_s, 2),
                  byte_identical=True,
                  utilization=spec_util)
    serving_entry("bench_serving_speculative", "random_off",
                  tok_per_s=useful / roff_s, results=roff_res)
    serving_entry("bench_serving_speculative", "random_ngram",
                  tok_per_s=useful / rspec_s, results=rspec_res,
                  spec_speedup=round(roff_s / rspec_s, 2),
                  byte_identical=True,
                  acceptance_rate=round(rand_accept, 3))
    SERVING["bench_serving_speculative"]["k_sweep"] = sweep


def bench_serving_shared_prefix(quick: bool):
    """Chunked prefill + COW prefix sharing vs the PR-1 engine (whole-prompt
    bucketed prefill, no sharing) on a shared-prefix trace — the
    pipeline-rerun workload the paper motivates: every request repeats a
    long common prompt prefix and adds a short novel suffix.

    Two claims are quantified: (1) prefix sharing + chunking raises
    tokens/sec on the same trace; (2) chunked prefill bounds the decode
    stall — max inter-token latency stays near one chunk's cost instead of
    a whole long prefill (compare itl_ms_max / itl_ms_p99 between rows).
    """
    import jax

    from repro.configs import ARCHS, reduced
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine, Request

    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(1)
    n = 8 if quick else 24
    prefix = list(rng.integers(1, cfg.vocab_size, 96))
    trace = [
        Request(
            f"s{i}",
            prefix + list(rng.integers(1, cfg.vocab_size, rng.integers(4, 33))),
            max_new_tokens=int(rng.integers(8, 33)),
        )
        for i in range(n)
    ]
    useful = sum(r.max_new_tokens for r in trace)
    max_len = 96 + 32 + 32
    slots = 8

    pr1 = ContinuousBatchingEngine(      # PR-1 behaviour
        cfg, params, max_len=max_len, max_slots=slots, page_size=16,
        prefill_chunk=None, prefix_sharing=False,
    )
    new = ContinuousBatchingEngine(      # this PR: chunked + COW sharing
        cfg, params, max_len=max_len, max_slots=slots, page_size=16,
        prefill_chunk=32, prefix_sharing=True,
    )

    def one_run(engine):
        for k in engine.cache.stats:    # stats describe this run only
            engine.cache.stats[k] = 0
        t0 = time.perf_counter()
        out = _drain(engine, _fresh(trace))
        return time.perf_counter() - t0, out, dict(engine.cache.stats)

    _drain(pr1, _fresh(trace))  # warm: compile each path
    _drain(new, _fresh(trace))
    # background load on shared CPU swings >2x between runs; alternate the
    # engines and take each one's best so drift doesn't pick the winner
    pr1_s, pr1_res, _ = one_run(pr1)
    new_s, new_res, new_stats = one_run(new)
    for _ in range(2):
        s, r, _ = one_run(pr1)
        if s < pr1_s:
            pr1_s, pr1_res = s, r
        s, r, st = one_run(new)
        if s < new_s:
            new_s, new_res, new_stats = s, r, st

    row("serve_sharedprefix_pr1", pr1_s * 1e6,
        f"tok_per_s={useful/pr1_s:.1f};{_latency_summary(pr1_res)}")
    reused = new_stats["prefix_tokens_reused"]
    row("serve_sharedprefix_cow", new_s * 1e6,
        f"tok_per_s={useful/new_s:.1f};speedup={pr1_s/new_s:.2f}x;"
        f"prefix_tokens_reused={reused};{_latency_summary(new_res)}")

    SERVING["bench_serving_shared_prefix"] = {"config": {
        "arch": cfg.name, "requests": n, "prefix_len": 96,
        "suffix_len": [4, 32], "max_new": [8, 32], "slots": slots,
        "best_of": 3,
    }}
    serving_entry("bench_serving_shared_prefix", "pr1_whole_prefill",
                  tok_per_s=useful / pr1_s, results=pr1_res)
    serving_entry("bench_serving_shared_prefix", "chunked_cow",
                  tok_per_s=useful / new_s, results=new_res,
                  speedup_vs_pr1=round(pr1_s / new_s, 2),
                  prefix_tokens_reused=int(reused))


def bench_serving_rerun(quick: bool):
    """Tiered KV cache on the pipeline-RERUN workload: the same prompt set
    served repeatedly with full idle drains in between — the notebook-rerun
    shape the paper motivates (rerun the pipeline, prompt prefixes
    identical, but no request is live when the next burst lands).

    Without tiers, prefix pages free when the last stream of a burst
    finishes, so every burst re-prefills the prefix from scratch and only
    WITHIN-burst COW sharing reuses tokens. With tiers, zero-refcount
    prefix pages park on-device and later bursts revive them, so the
    prefix prefill is skipped entirely. The headline numbers: burst-2+
    ``prefix_tokens_reused`` (tiers-on must be >= 2x tiers-off — the PR's
    acceptance bound) and the tier hit counters. Alternated best-of, like
    the other serving benches; reuse counters come from the LAST round so
    warm parked state reflects steady rerun traffic.
    """
    import jax

    from repro.configs import ARCHS, reduced
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine, Request
    from repro.serving.metrics import UtilizationMetrics

    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(5)
    n = 6 if quick else 12
    bursts = 2 if quick else 3
    # every request is a DISTINCT 96-token prompt (one per "pipeline cell")
    # plus a short novel suffix — so within a burst there is nothing to
    # share and all cross-burst reuse is the tier machinery's doing
    trace = [
        Request(
            f"t{i}",
            list(rng.integers(1, cfg.vocab_size, 96))
            + list(rng.integers(1, cfg.vocab_size, rng.integers(4, 17))),
            max_new_tokens=int(rng.integers(8, 17)),
        )
        for i in range(n)
    ]
    useful = bursts * sum(r.max_new_tokens for r in trace)
    max_len = 96 + 16 + 16
    slots = 4

    # pool sized for the rerun working set (n prompts x ~7 pages each) so
    # parked chains survive a full burst; the host tier catches overflow —
    # an undersized device pool just LRU-thrashes (each admission evicting
    # the chain the next prompt needs), which is a pool-sizing problem,
    # not a tier-policy one
    num_pages = n * (96 // 16 + 2) + 2 * slots
    def make(tiers: bool):
        return ContinuousBatchingEngine(
            cfg, params, max_len=max_len, max_slots=slots, page_size=16,
            prefill_chunk=32, num_pages=num_pages, kv_tiers=tiers,
            host_pages=num_pages if tiers else 0,
        )

    def one_run(engine):
        """Serve ``bursts`` identical bursts, draining to idle between
        them; returns wall time + the per-burst prefix reuse counts."""
        engine.utilization = UtilizationMetrics()
        reused = []
        t0 = time.perf_counter()
        for _ in range(bursts):
            base = engine.cache.stats["prefix_tokens_reused"]
            _drain(engine, _fresh(trace))
            reused.append(engine.cache.stats["prefix_tokens_reused"] - base)
        return time.perf_counter() - t0, reused

    engines = {"tiers_on": make(True), "tiers_off": make(False)}
    for engine in engines.values():
        one_run(engine)  # warm: compile + (tiers_on) park the prefix
    rounds = 1 if quick else 3
    best: dict = {}
    last: dict = {}
    for _ in range(rounds):
        for name, engine in engines.items():
            s, reused = one_run(engine)
            last[name] = reused
            if name not in best or s < best[name][0]:
                best[name] = (s, reused)
    on_s, _ = best["tiers_on"]
    off_s, _ = best["tiers_off"]
    # reuse counts are deterministic given warm state — report the last
    # round's, which reflects steady rerun traffic for both arms
    on_reuse, off_reuse = last["tiers_on"], last["tiers_off"]
    rerun_on = sum(on_reuse[1:]) / max(bursts - 1, 1)
    rerun_off = sum(off_reuse[1:]) / max(bursts - 1, 1)
    tiers = engines["tiers_on"].tiers
    assert tiers is not None and engines["tiers_off"].tiers is None

    row("serve_rerun_tiers_off", off_s * 1e6,
        f"tok_per_s={useful/off_s:.1f};burst2_prefix_reused={rerun_off:.0f}")
    row("serve_rerun_tiers_on", on_s * 1e6,
        f"tok_per_s={useful/on_s:.1f};burst2_prefix_reused={rerun_on:.0f};"
        f"reuse_ratio={rerun_on/max(rerun_off, 1):.1f}x;"
        f"tier_hits=dev{tiers.counters['device_hits']}")

    SERVING["bench_serving_rerun"] = {"config": {
        "arch": cfg.name, "requests_per_burst": n, "bursts": bursts,
        "prompt_len": [96 + 4, 96 + 16], "distinct_prompts": True,
        "max_new": [8, 16], "slots": slots, "prefill_chunk": 32,
        "best_of": rounds,
    }}
    serving_entry("bench_serving_rerun", "tiers_off",
                  tok_per_s=useful / off_s,
                  prefix_tokens_reused_per_burst=off_reuse,
                  rerun_burst_prefix_reused=round(rerun_off, 1))
    serving_entry("bench_serving_rerun", "tiers_on",
                  tok_per_s=useful / on_s,
                  prefix_tokens_reused_per_burst=on_reuse,
                  rerun_burst_prefix_reused=round(rerun_on, 1),
                  rerun_reuse_ratio_vs_off=round(
                      rerun_on / max(rerun_off, 1), 2),
                  tier_counters={k: v for k, v in tiers.counters.items()
                                 if not k.endswith("_s")},
                  utilization=engines["tiers_on"].utilization.summary())


def bench_serving_prefill_heavy(quick: bool):
    """Kernel-path vs ref-path chunked prefill on a prefill-heavy trace:
    long prompts, tiny max_new — the regime where TTFT is bounded by the
    prefill lowering (ROADMAP: the last non-Pallas hot path until this PR).

    Two engines differ ONLY in ``attn_impl``: "xla_chunked" pins the
    reference lowering, "auto" dispatches the Pallas chunk-prefill (and
    decode) kernels on TPU and resolves to the same reference path off-TPU
    — so on a CPU host the two rows are one lowering timed twice, while on
    a TPU host the same bench measures the kernels. Best-of-3 with the
    engines alternated, like the shared-prefix bench."""
    import jax

    from repro.configs import ARCHS, reduced
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine, Request

    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(2)
    n = 6 if quick else 16
    trace = [
        Request(
            f"p{i}",
            list(rng.integers(1, cfg.vocab_size, rng.integers(96, 161))),
            max_new_tokens=int(rng.integers(4, 9)),
        )
        for i in range(n)
    ]
    useful = sum(r.max_new_tokens for r in trace)
    max_len = 192
    slots = 4
    chunk = 32

    def make(attn_impl):
        return ContinuousBatchingEngine(
            cfg, params, max_len=max_len, max_slots=slots, page_size=16,
            prefill_chunk=chunk, attn_impl=attn_impl,
        )

    ref_eng, kern_eng = make("xla_chunked"), make("auto")

    def one_run(engine):
        t0 = time.perf_counter()
        out = _drain(engine, _fresh(trace))
        return time.perf_counter() - t0, out

    _drain(ref_eng, _fresh(trace))   # warm: compile each path
    _drain(kern_eng, _fresh(trace))
    ref_s, ref_res = one_run(ref_eng)
    kern_s, kern_res = one_run(kern_eng)
    for _ in range(2):               # alternated best-of-3
        s, r = one_run(ref_eng)
        if s < ref_s:
            ref_s, ref_res = s, r
        s, r = one_run(kern_eng)
        if s < kern_s:
            kern_s, kern_res = s, r

    row("serve_prefillheavy_ref", ref_s * 1e6,
        f"tok_per_s={useful/ref_s:.1f};{_latency_summary(ref_res)}")
    row("serve_prefillheavy_kernel", kern_s * 1e6,
        f"tok_per_s={useful/kern_s:.1f};ttft_ratio_vs_ref="
        f"{np.median([x.ttft for x in kern_res])/np.median([x.ttft for x in ref_res]):.2f};"
        f"{_latency_summary(kern_res)}")

    SERVING["bench_serving_prefill_heavy"] = {"config": {
        "arch": cfg.name, "requests": n, "prompt_len": [96, 160],
        "max_new": [4, 8], "slots": slots, "prefill_chunk": chunk,
        "max_len": max_len, "best_of": 3,
        "kernel_backend": jax.default_backend(),
        # off-TPU "auto" resolves to the ref lowering: equal rows then
        # mean "same lowering", not "kernel measured"
        "kernel_resolves_to_ref": jax.default_backend() != "tpu",
    }}
    serving_entry("bench_serving_prefill_heavy", "ref_prefill",
                  tok_per_s=useful / ref_s, results=ref_res)
    serving_entry("bench_serving_prefill_heavy", "kernel_prefill",
                  tok_per_s=useful / kern_s, results=kern_res,
                  ttft_p50_ratio_vs_ref=round(
                      float(np.median([x.ttft for x in kern_res])
                            / np.median([x.ttft for x in ref_res])), 3))


def bench_serving_ssm(quick: bool):
    """Continuous batching for recurrent models: the SSM slot-state engine
    vs the lockstep baseline on a mixed-length Mamba2 trace.

    Same regime as ``bench_serving`` (mixed prompts, mixed max_new) but the
    model carries per-sequence recurrent state instead of a KV cache, so
    the comparison isolates the slot-state engine itself: O(1)-per-token
    state updates either amortized across a continuously-batched slot bank
    (SSM engine) or serialized behind the slowest request of each lockstep
    micro-batch. Alternated best-of-3, warmed, same protocol loop."""
    import jax

    from repro.configs import ARCHS, reduced
    from repro.launch.mesh import describe_mesh
    from repro.models import build_model
    from repro.serving import GenerationEngine, Request, SSMEngine
    from repro.serving.metrics import UtilizationMetrics

    cfg = reduced(ARCHS["mamba2-1.3b"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(4)
    n = 8 if quick else 24
    trace = [
        Request(
            f"s{i}",
            list(rng.integers(1, cfg.vocab_size, rng.integers(8, 97))),
            max_new_tokens=int(rng.integers(4, 49)),
        )
        for i in range(n)
    ]
    useful = sum(r.max_new_tokens for r in trace)
    max_len = 96 + 48
    slots = 8
    chunk = 32

    engines = {
        f"lockstep_b{slots}": GenerationEngine(
            cfg, params, max_len=max_len, max_batch=slots),
        "ssm": SSMEngine(
            cfg, params, max_len=max_len, max_slots=slots,
            prefill_chunk=chunk),
    }

    def one_run(engine):
        engine.utilization = UtilizationMetrics()  # gauge this run only
        t0 = time.perf_counter()
        out = _drain(engine, _fresh(trace))
        return time.perf_counter() - t0, out

    for engine in engines.values():
        _drain(engine, _fresh(trace))  # warm: compile each path
    rounds = 2 if quick else 3
    best = _best_of(engines, one_run, rounds)
    lock_s, lock_res = best[f"lockstep_b{slots}"]
    ssm_s, ssm_res = best["ssm"]

    row(f"serve_ssm_lockstep_b{slots}", lock_s * 1e6,
        f"tok_per_s={useful/lock_s:.1f}")
    row("serve_ssm", ssm_s * 1e6,
        f"tok_per_s={useful/ssm_s:.1f};speedup={lock_s/ssm_s:.2f}x;"
        f"{_latency_summary(ssm_res)}")

    SERVING["bench_serving_ssm"] = {"config": {
        "arch": cfg.name, "requests": n, "prompt_len": [8, 96],
        "max_new": [4, 48], "slots": slots, "max_len": max_len,
        "prefill_chunk": chunk, "useful_tokens": useful, "best_of": rounds,
        "mesh": describe_mesh(engines["ssm"].executor.mesh),
    }}
    serving_entry("bench_serving_ssm", f"lockstep_b{slots}",
                  tok_per_s=useful / lock_s, results=lock_res)
    serving_entry("bench_serving_ssm", "ssm", tok_per_s=useful / ssm_s,
                  results=ssm_res,
                  speedup_vs_lockstep=round(lock_s / ssm_s, 2),
                  utilization=engines["ssm"].utilization.summary())


def bench_fleet_recovery(quick: bool):
    """Fault-tolerance cost on the supervised serving fleet: the same trace
    served by a 2-worker FleetSupervisor with 0 vs 1 injected worker crash
    per run (alternated best-of-3). Reports delivered tok/s, client-
    observed p99 inter-token latency (bus delta timestamps — the crash gap
    lands in the ITL tail, which is exactly where a client would feel it),
    and the recovery latency (crash detected -> first token delivered past
    the crash boundary). The 1-crash run must still complete every request
    — crash-replay recovery is correctness here, the bench prices it."""
    import jax

    from repro.configs import ARCHS, reduced
    from repro.core import TopicBus
    from repro.core.faults import FaultInjector, WorkerKillRule
    from repro.models import build_model
    from repro.serving import ContinuousBatchingEngine, FleetConfig, FleetSupervisor

    cfg = reduced(ARCHS["smollm-360m"])
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(3)
    n = 6 if quick else 12
    max_new = 12
    payloads = [
        {"uid": f"f{i}",
         "prompt": [int(x) for x in
                    rng.integers(1, cfg.vocab_size, int(rng.integers(12, 33)))],
         "max_new_tokens": max_new,
         "temperature": 0.7 if i % 3 == 0 else 0.0,
         "seed": 100 + i}
        for i in range(n)
    ]
    uids = [p["uid"] for p in payloads]

    def factory():
        return ContinuousBatchingEngine(
            cfg, params, max_len=64, max_slots=4, page_size=16,
            prefill_chunk=16)

    def bus_itls(bus) -> list[float]:
        per: dict[str, list] = {}
        for m in bus.read("responses"):
            if m.value["event"] == "delta":
                per.setdefault(m.value["uid"], []).append(
                    (m.value["index"], m.ts))
            # client-observed gaps, in delivered-index order
        return [b - a for v in per.values()
                for (_, a), (_, b) in zip(sorted(v), sorted(v)[1:])]

    def one_run(crash: bool):
        d = tempfile.mkdtemp()
        try:
            bus = TopicBus(Path(d) / "bus")
            inj = FaultInjector(worker_rules=[
                WorkerKillRule(after_tokens=2 * max_new, times=1)
            ]) if crash else None
            sup = FleetSupervisor(
                bus, factory,
                FleetConfig(workers=2, autoscale=False, beat_interval_s=0.05,
                            max_restarts=2, seed_base=9),
                injector=inj)
            try:
                for p in payloads:
                    sup.submit(p)
                t0 = time.perf_counter()
                assert sup.run(expected=uids, timeout_s=300), \
                    "fleet bench run did not drain"
                wall = time.perf_counter() - t0
            finally:
                sup.shutdown()
            states = sup.results()
            delivered = sum(len(s.tokens) for s in states.values())
            assert all(s.finish_reason in ("length", "stop")
                       for s in states.values()), "request lost across crash"
            if crash:
                assert sup.metrics.crashes >= 1, "kill rule never fired"
            return wall, delivered, bus_itls(bus), sup.metrics
        finally:
            shutil.rmtree(d, ignore_errors=True)

    one_run(False)  # warm: worker engines compile once per process
    rounds = 1 if quick else 3
    best: dict[bool, tuple] = {}
    for _ in range(rounds):  # alternated best-of, like the engine benches
        for crash in (False, True):
            r = one_run(crash)
            if crash not in best or r[0] < best[crash][0]:
                best[crash] = r

    clean_w, clean_tok, clean_itls, _ = best[False]
    crash_w, crash_tok, crash_itls, fm = best[True]
    p99 = lambda xs: float(np.percentile(xs, 99) * 1e3) if xs else 0.0
    rec = fm.recovery_s
    row("serve_fleet_clean", clean_w * 1e6,
        f"tok_per_s={clean_tok/clean_w:.1f};itl_ms_p99={p99(clean_itls):.1f}")
    row("serve_fleet_1crash", crash_w * 1e6,
        f"tok_per_s={crash_tok/crash_w:.1f};"
        f"slowdown={crash_w/clean_w:.2f}x;"
        f"recovery_s={max(rec) if rec else 0:.3f};"
        f"itl_ms_p99={p99(crash_itls):.1f}")

    SERVING["bench_fleet_recovery"] = {"config": {
        "arch": cfg.name, "requests": n, "prompt_len": [12, 32],
        "max_new": max_new, "workers": 2, "kill_after_tokens": 2 * max_new,
        "best_of": rounds,
    }}
    serving_entry("bench_fleet_recovery", "fleet_clean",
                  tok_per_s=clean_tok / clean_w,
                  itl_ms_p99=round(p99(clean_itls), 2))
    serving_entry("bench_fleet_recovery", "fleet_1crash",
                  tok_per_s=crash_tok / crash_w,
                  itl_ms_p99=round(p99(crash_itls), 2),
                  slowdown_vs_clean=round(crash_w / clean_w, 2),
                  crashes=fm.crashes, resubmitted=fm.resubmitted,
                  duplicate_deltas_suppressed=fm.duplicate_deltas,
                  recovery_s_mean=round(float(np.mean(rec)), 3) if rec else None,
                  recovery_s_max=round(float(np.max(rec)), 3) if rec else None)


def bench_kernels(quick: bool):
    """Pallas kernels (interpret mode) vs jnp reference — correctness + time."""
    import jax

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    b, s, h, kvh, dh = 1, 256, 4, 2, 64
    q = rng.standard_normal((b, s, h, dh)).astype(np.float32)
    k = rng.standard_normal((b, s, kvh, dh)).astype(np.float32)
    v = rng.standard_normal((b, s, kvh, dh)).astype(np.float32)
    f_ref = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True))
    f_chk = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True, impl="xla_chunked"))
    n = 3 if quick else 20
    us_ref = timeit(lambda: jax.block_until_ready(f_ref(q, k, v)), n)
    us_chk = timeit(lambda: jax.block_until_ready(f_chk(q, k, v)), n)
    err = float(np.abs(np.asarray(f_chk(q, k, v)) - np.asarray(f_ref(q, k, v))).max())
    row("attn_naive_xla", us_ref, "impl=naive")
    row("attn_chunked_xla", us_chk, f"max_err={err:.2e}")

    x = rng.standard_normal((1, 256, 4, 32)).astype(np.float32)
    dt = (0.1 + 0.9 * rng.random((1, 256, 4))).astype(np.float32)
    A = (-rng.random(4) - 0.1).astype(np.float32)
    Bm = (rng.standard_normal((1, 256, 64)) / 8).astype(np.float32)
    Cm = (rng.standard_normal((1, 256, 64)) / 8).astype(np.float32)
    s_seq = jax.jit(lambda *a: ref.ssd_sequential(*a)[0])
    s_chk = jax.jit(lambda *a: ref.ssd_chunked(*a, chunk=64)[0])
    us_seq = timeit(lambda: jax.block_until_ready(s_seq(x, dt, A, Bm, Cm)), n)
    us_chk2 = timeit(lambda: jax.block_until_ready(s_chk(x, dt, A, Bm, Cm)), n)
    err = float(np.abs(np.asarray(s_chk(x, dt, A, Bm, Cm)) - np.asarray(s_seq(x, dt, A, Bm, Cm))).max())
    row("ssd_sequential_xla", us_seq, "impl=recurrence")
    row("ssd_chunked_xla", us_chk2, f"max_err={err:.2e};speedup={us_seq/us_chk2:.1f}x")


def bench_recovery(quick: bool):
    """C6: workflow wall time without vs with injected pod failures."""
    from repro.core import ArtifactStore, Notebook, TopicBus, WorkflowScheduler, split_pipeline
    from repro.core.faults import FaultInjector, KillRule
    from repro.core.scheduler import RetryPolicy

    srcs = ["import time\ntime.sleep(0.05)\na = 1",
            "# %%pipe\nb = a + 1", "# %%pipe\nc = b * 2"]

    def run(faults=None):
        d = tempfile.mkdtemp()
        try:
            nb = Notebook.from_sources(srcs)
            g = split_pipeline(nb)
            sched = WorkflowScheduler(
                g, TopicBus(Path(d) / "bus"), ArtifactStore(Path(d) / "store"),
                retry=RetryPolicy(max_attempts=4, backoff_s=0.02),
                fault_injector=faults)
            t0 = time.perf_counter()
            arts = sched.run(timeout_s=60)
            assert arts["c"] == 4
            return time.perf_counter() - t0
        finally:
            shutil.rmtree(d, ignore_errors=True)

    clean = run()
    chaotic = run(FaultInjector([KillRule(step="cell0", after_s=0.0, times=1)]))
    row("workflow_clean", clean * 1e6, "steps=3")
    row("workflow_chaos_1kill", chaotic * 1e6,
        f"recovery_overhead={(chaotic-clean)*1e3:.0f}ms")


def bench_scaling(quick: bool):
    """Scheduler overhead vs #steps (pods)."""
    from repro.core import ArtifactStore, Notebook, TopicBus, WorkflowScheduler, split_pipeline
    from repro.core.scheduler import RetryPolicy

    for n in ([4, 8] if quick else [4, 16, 32]):
        srcs = ["a0 = 1"] + [f"# %%pipe\na{i} = a{i-1} + 1" for i in range(1, n)]
        d = tempfile.mkdtemp()
        try:
            g = split_pipeline(Notebook.from_sources(srcs))
            sched = WorkflowScheduler(
                g, TopicBus(Path(d) / "bus"), ArtifactStore(Path(d) / "store"),
                retry=RetryPolicy(backoff_s=0.01))
            t0 = time.perf_counter()
            sched.run(timeout_s=120)
            wall = time.perf_counter() - t0
            row(f"scheduler_pods_{n}", wall * 1e6, f"us_per_step={wall/n*1e6:.0f}")
        finally:
            shutil.rmtree(d, ignore_errors=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only benches whose name contains SUBSTR "
                         "(e.g. --only serving regenerates the serving "
                         "sections of BENCH_serving.json)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    t0 = time.time()
    benches = (bench_split, bench_bus, bench_storage, bench_ckpt,
               bench_kernels, bench_recovery, bench_scaling, bench_step,
               bench_serving, bench_serving_shared_prefix,
               bench_serving_rerun, bench_serving_prefill_heavy,
               bench_serving_low_load, bench_serving_speculative,
               bench_serving_ssm, bench_fleet_recovery)
    for bench in benches:
        if args.only and args.only not in bench.__name__:
            continue
        bench(args.quick)
    print(f"# total {time.time()-t0:.0f}s")
    out = Path("experiments")
    out.mkdir(exist_ok=True)
    (out / "bench_results.json").write_text(
        json.dumps([{"name": n, "us": u, "derived": d} for n, u, d in ROWS], indent=1))
    if SERVING:
        import jax

        SERVING["meta"] = {
            "quick": args.quick,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
        }
        path = out / "BENCH_serving.json"
        # merge over the checked-in sections: a filtered run (--only) must
        # refresh only the benches it actually ran, never drop the rest
        merged = {}
        if path.exists():
            try:
                merged = json.loads(path.read_text())
            except json.JSONDecodeError:
                pass
        merged.update(SERVING)
        path.write_text(json.dumps(merged, indent=1, sort_keys=True))
        print(f"# serving results -> {path}")


if __name__ == "__main__":
    main()
