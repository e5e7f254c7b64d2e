"""Serving driver: protocol engines behind a bus topic, streaming deltas.

Requests land on the ``requests`` topic (Kafka analogue). ONE engine-agnostic
worker loop drives any :class:`repro.serving.EngineCore` implementation —
paged continuous batching or the lockstep baseline — through the same
lifecycle: pull up to ``engine.capacity()`` messages, parse them with the
shared boundary parser (every sampling field survives; the old per-engine
parsers dropped ``temperature``), ``submit()``, and publish each
:class:`StreamEvent` to ``responses`` as it happens — per-token ``delta``
messages first, then one terminal ``finish`` message with the full output
and a typed ``finish_reason``, so consumers observe streaming output before
completion.

Admission order is pluggable (``--admission fifo|priority|deadline``).
Prompts prefill in fixed-size chunks interleaved with decode
(``--prefill-chunk``, 0 restores whole-prompt prefill) and identical prompt
prefixes are served from shared copy-on-write pages (``--no-prefix-sharing``
to disable; ``--shared-prefix N`` synthesizes the pipeline-rerun workload
that exercises it). By default the paged engine runs its *fused* step — the
step's prefill chunk and every decode slot go down in one mixed dispatch
(``--step-mode interleaved`` restores the two-dispatch step for A/B;
``--token-budget`` caps rows per fused step). The utilization line reports
the per-dispatch batch composition (decode/prefill/padded rows and the
fused-dispatch fraction) alongside the occupancy gauges.

With prefix sharing on, the paged engine also runs the tiered KV cache
(``serving/kv_tiers.py``): finished prompts' prefix pages park in a
reclaim-under-pressure LRU instead of freeing, optionally spill to host RAM
(``--host-pages N``) and persist through the artifact store
(``--persist-dir PATH``) so identical reruns skip their prefill, and
``--kv-quant int8`` stores pages quantized for ~2x KV capacity per byte.
The tier gauges and hit counters appear in the utilization line.

The paged engine's executor runs under ``shard_map`` on a ``("model",)``
mesh; ``--mesh auto`` (default) picks the largest tensor-parallel degree
the model's head counts allow over the local devices, ``--mesh N`` forces
an explicit size (1 disables sharding). The run prints p50/p90/p99
time-to-first-token and inter-token latency plus the per-step decode-slot
occupancy and page-pool utilization gauges. The HPA analogue watches
consumer lag and scales workers in [min,max]. CPU-runnable with reduced
configs:

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
      --requests 24 --shared-prefix 32
"""

from __future__ import annotations

import argparse
import threading
import time
from pathlib import Path

import jax


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="lockstep micro-batch size / paged slot count")
    ap.add_argument("--engine", choices=["paged", "lockstep"], default="paged",
                    help="'paged' (default) picks the continuous-batching "
                         "engine for the config's family — page-pool KV for "
                         "dense/moe/vlm, the recurrent-state SSM engine for "
                         "ssm/hybrid — and fails loudly "
                         "(UnsupportedConfigError) when no continuous-"
                         "batching engine supports the config; 'lockstep' "
                         "forces the micro-batching baseline")
    ap.add_argument("--admission", choices=["fifo", "priority", "deadline"],
                    default="fifo", help="admission policy for every worker")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="paged engine: prefill chunk size; 0 restores the "
                         "whole-prompt bucketed prefill")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="paged engine: disable COW prefix-page sharing")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend a common N-token prefix to every request "
                         "(pipeline-rerun workload; exercises prefix sharing)")
    ap.add_argument("--mesh", default="auto",
                    help="paged engine: tensor-parallel mesh size for the "
                         "sharded executor — 'auto' picks the largest "
                         "feasible degree over local devices, an integer "
                         "forces that many (1 disables sharding)")
    ap.add_argument("--step-mode", default="fused",
                    choices=["fused", "interleaved"],
                    help="paged engine: 'fused' (default) runs every decode "
                         "slot and the step's prefill chunk in ONE mixed "
                         "dispatch; 'interleaved' keeps the two-dispatch "
                         "pre-fusion step for A/B comparison — streams are "
                         "byte-identical either way")
    ap.add_argument("--token-budget", type=int, default=0,
                    help="paged engine, fused mode: cap decode rows + chunk "
                         "tokens per step (Sarathi-style); 0 disables the cap")
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="paged engine: KV page precision — 'int8' stores "
                         "pages quantized with per-page-per-head scales "
                         "(~2x sequences per pool byte; dequantization is "
                         "fused into the paged attention kernels)")
    ap.add_argument("--host-pages", type=int, default=0, metavar="N",
                    help="paged engine: host-RAM spill tier capacity in "
                         "pages — cold parked prefix pages demote to host "
                         "buffers and prefetch back on prefix hits; 0 "
                         "disables the host tier")
    ap.add_argument("--persist-dir", default=None, metavar="PATH",
                    help="paged engine: ArtifactStore root for write-through "
                         "prefix-page persistence — spilled pages survive "
                         "restarts and re-serve identical prompt prefixes "
                         "across runs")
    ap.add_argument("--spec", default="off",
                    choices=["off", "ngram", "draft"],
                    help="paged engine: speculative decoding proposer — "
                         "'ngram' self-speculates from the request's own "
                         "prompt+output history (no second model), 'draft' "
                         "runs a small draft model (--draft-config) on its "
                         "own paged cache; streams are byte-identical to "
                         "'off' either way")
    ap.add_argument("--spec-k", type=int, default=4, metavar="K",
                    help="speculative decoding: drafted tokens per bundle "
                         "(the verify dispatch scores K drafts + 1 bonus "
                         "position in one call)")
    ap.add_argument("--draft-config", default=None, metavar="ARCH",
                    help="--spec draft: arch name for the draft model "
                         "(e.g. 'smollm-360m'; '-reduced' suffix honored, "
                         "and --reduced applies to the draft too); fresh "
                         "seed-derived draft weights are initialized at "
                         "startup")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "pallas", "pallas_interpret",
                             "xla_chunked", "naive"],
                    help="paged engine: attention lowering for decode and "
                         "chunked prefill — 'auto' uses the Pallas kernels "
                         "on TPU and the XLA reference elsewhere; 'pallas' "
                         "on a non-TPU backend raises")
    ap.add_argument("--ssd-impl", default="auto",
                    choices=["auto", "pallas", "pallas_interpret",
                             "xla_chunked", "naive"],
                    help="ssm engine: SSD scan/decode lowering — same "
                         "auto/pallas contract as --attn-impl")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="run the supervised fleet instead of the flat "
                         "worker pool: a FleetSupervisor with N initial "
                         "engine workers, heartbeat probes, crash-replay "
                         "recovery and lag/occupancy autoscaling "
                         "(serving/fleet.py); 0 keeps the legacy path")
    ap.add_argument("--role", choices=["driver", "worker"], default="driver",
                    help="'worker': run ONE fleet engine-worker loop against "
                         "an existing --workdir bus (a supervisor elsewhere "
                         "publishes fleet.work) and exit when the work topic "
                         "drains — the multi-process deployment shape")
    ap.add_argument("--worker-name", default="w0",
                    help="--role worker: this worker's pod name")
    ap.add_argument("--workdir", default="experiments/serve_run")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.configs import get_arch, reduced
    from repro.core import TopicBus
    from repro.core.autoscaler import Autoscaler, AutoscalerConfig
    from repro.core.events import EventLog
    from repro.core.registry import ServiceRegistry
    from repro.launch.mesh import describe_mesh, make_serving_mesh
    from repro.models import build_model
    from repro.serving import (
        ContinuousBatchingEngine,
        DeadlineAdmission,
        FIFOAdmission,
        GenerationEngine,
        PriorityAdmission,
        SSMEngine,
        UnsupportedConfigError,
        format_latency,
        request_from_message,
    )
    from repro.serving.executor import (
        default_serving_mesh,
        init_serving_params,
        set_default_serving_mesh,
    )
    from repro.serving.metrics import UtilizationMetrics

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    paged_ok = not cfg.is_encoder_decoder and cfg.family in ("dense", "moe", "vlm")
    ssm_ok = not cfg.is_encoder_decoder and cfg.family in ("ssm", "hybrid")
    use_paged = args.engine == "paged" and paged_ok
    use_ssm = args.engine == "paged" and ssm_ok
    if args.engine == "paged" and not (use_paged or use_ssm):
        # no silent lockstep downgrade: the caller asked for continuous
        # batching, and neither the page-pool nor the recurrent-state
        # engine can serve this config
        raise UnsupportedConfigError(
            f"no continuous-batching engine supports {cfg.name} "
            f"(family={cfg.family!r}, encoder_decoder="
            f"{cfg.is_encoder_decoder}); pass --engine lockstep to serve "
            f"it with the micro-batching baseline"
        )
    sharded = use_paged or use_ssm  # both executors run under shard_map
    if sharded and args.mesh != "auto":
        set_default_serving_mesh(make_serving_mesh(int(args.mesh)))
    mesh_desc = describe_mesh(default_serving_mesh(cfg)) if sharded else "n/a"
    workdir = Path(args.workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    bus = TopicBus(workdir / "bus")
    events = EventLog(bus, workflow=f"serve-{cfg.name}")
    registry = ServiceRegistry(bus)

    if sharded:
        # validates the mesh ONCE in the main thread (a bad --mesh N fails
        # fast here, not inside every worker) and creates the weights in
        # their shardings, so no chip ever holds the whole model and all
        # workers share one placed copy
        params = init_serving_params(cfg, jax.random.key(0))
    else:
        params = build_model(cfg).init(jax.random.key(0))
    shared = list(range(2, 2 + args.shared_prefix))
    max_len = 64 + args.shared_prefix + args.max_new

    # ---- producer: enqueue requests (mixed sampling params, so the full
    # Request surface travels through the bus, not just uid/prompt) ----
    if args.role == "driver":
        for i in range(args.requests):
            bus.publish(
                "requests",
                {"uid": f"r{i}",
                 "prompt": shared + [1 + (i % 30), 2, 3 + (i % 7)],
                 "max_new_tokens": args.max_new,
                 "temperature": 0.7 if i % 4 == 3 else 0.0,
                 "seed": i,
                 "priority": i % 3},
            )

    group = "servers"
    scaler = Autoscaler(
        bus, "requests", group,
        AutoscalerConfig(min_replicas=1, max_replicas=4,
                         target_lag_per_replica=args.max_batch * 2),
        events=events,
    )
    policies = {"fifo": FIFOAdmission, "priority": PriorityAdmission,
                "deadline": DeadlineAdmission}

    # --reduced shrinks the target; a draft arch named on the CLI must
    # shrink with it, or the "small" draft model is full-size on CPU
    draft_config = args.draft_config
    if draft_config and args.reduced and not draft_config.endswith("-reduced"):
        draft_config = f"{draft_config}-reduced"

    def make_engine():
        admission = policies[args.admission]()
        if use_paged:
            return ContinuousBatchingEngine(
                cfg, params, max_len=max_len,
                max_slots=max(args.max_batch, 2),
                prefill_chunk=args.prefill_chunk or None,
                prefix_sharing=not args.no_prefix_sharing,
                admission=admission,
                attn_impl=args.attn_impl,
                step_mode=args.step_mode,
                token_budget=args.token_budget or None,
                kv_quant=args.kv_quant,
                host_pages=args.host_pages,
                persist_dir=args.persist_dir,
                speculative=args.spec,
                spec_k=args.spec_k,
                draft_config=draft_config,
            )
        if use_ssm:
            return SSMEngine(
                cfg, params, max_len=max_len,
                max_slots=max(args.max_batch, 2),
                prefill_chunk=args.prefill_chunk or None,
                admission=admission,
                attn_impl=args.attn_impl,
                ssd_impl=args.ssd_impl,
            )
        return GenerationEngine(cfg, params, max_len=max_len,
                                max_batch=args.max_batch, admission=admission)

    if args.role == "worker":
        return _run_worker(args, bus, make_engine)
    if args.fleet:
        return _run_fleet(args, bus, events, make_engine)

    done: dict[str, list[int]] = {}
    failures: list[tuple[int, BaseException]] = []  # (worker id, error)
    latencies: list = []  # Results, for TTFT/ITL percentiles
    utilization = UtilizationMetrics()  # merged across workers
    lock = threading.Lock()

    def finish(uid: str, result) -> None:
        """Publish one terminal response and record it for the driver."""
        bus.publish("responses", {
            "uid": uid, "event": "finish",
            "tokens": result.tokens if result else [],
            "finish_reason": result.finish_reason.value if result else "rejected",
            "error": result.error if result else None,
        })
        with lock:
            done[uid] = result.tokens if result else []
            if result is not None:
                latencies.append(result)

    def worker(wid: int, stop: threading.Event):
        """THE worker loop: engine-agnostic, protocol-driven, streaming."""
        engine = None
        try:
            engine = make_engine()
            registry.register("generate", f"pod://server-{wid}",
                              f"server-{wid}")
            _worker_loop(engine, stop, {})
        except BaseException as e:
            # a worker that raised (a compile error, a device fault) would
            # raise again if replaced: stop the whole run instead
            with lock:
                failures.append((wid, e))
            stop.set()
        finally:
            if engine is not None:
                cache = getattr(engine, "cache", None)
                if getattr(cache, "tiers", None) is not None:
                    # drain parked prefixes to host/persist so a
                    # --persist-dir rerun of the same prompts revives them
                    cache.flush_tiers()
                    engine._record_tiers()  # fold the flush into the gauges
                with lock:
                    utilization.merge(engine.utilization)

    def _worker_loop(engine, stop, handles):
        while not stop.is_set():
            pulled = 0
            for m in bus.consume("requests", group, limit=engine.capacity()):
                try:
                    req = request_from_message(m.value)
                except (ValueError, KeyError, TypeError) as e:
                    v = m.value
                    uid = v.get("uid", "?") if isinstance(v, dict) else "?"
                    bus.publish("responses", {
                        "uid": str(uid), "event": "finish", "tokens": [],
                        "finish_reason": "rejected", "error": str(e),
                    })
                    with lock:
                        done[str(uid)] = []
                else:
                    h = engine.submit(req)
                    if h.done:  # rejected at the API boundary
                        finish(h.uid, h.result())
                    else:
                        handles[h.uid] = h
                        pulled += 1
                bus.commit("requests", group, m.offset + 1)
            if engine.idle:
                if not pulled and bus.lag("requests", group) == 0:
                    return
                time.sleep(0.01)
                continue
            for ev in engine.step():
                if ev.kind == "token":
                    bus.publish("responses", {
                        "uid": ev.uid, "event": "delta",
                        "token": ev.token, "index": ev.index,
                    })
                elif ev.kind == "finish":
                    h = handles.pop(ev.uid, None)
                    finish(ev.uid, h.result() if h else None)

    threads: list[threading.Thread] = []
    stop = threading.Event()
    t0 = time.time()
    desired, _ = scaler.observe()
    while (len(done) < args.requests and not failures
           and time.time() - t0 < 600):
        desired, changed = scaler.observe()
        while len([t for t in threads if t.is_alive()]) < desired:
            wid = len(threads)
            t = threading.Thread(target=worker, args=(wid, stop), daemon=True)
            t.start()
            threads.append(t)
        time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    if failures:
        wid, err = failures[0]
        raise RuntimeError(
            f"serving worker server-{wid} failed; run stopped"
        ) from err

    wall = time.time() - t0
    print(f"served {len(done)}/{args.requests} requests in {wall:.1f}s "
          f"({len(done)*args.max_new/wall:.1f} tok/s), "
          f"engine={'paged' if use_paged else 'ssm' if use_ssm else 'lockstep'}, "
          f"admission={args.admission}, mesh={mesh_desc}, "
          f"peak workers={len(threads)}")
    summary = format_latency(latencies)
    if summary != "no_latency_data":
        print(summary)
    print("utilization:", utilization.format())
    autoscales = events.history("autoscale")
    print("autoscale events:", [(e["old"], e["new"]) for e in autoscales])
    assert len(done) == args.requests

    # streaming invariant: every served request's first delta is observable
    # on the bus BEFORE its terminal finish message
    first_delta: dict[str, int] = {}
    finish_at: dict[str, int] = {}
    for m in bus.read("responses"):
        uid, event = m.value["uid"], m.value["event"]
        if event == "delta":
            first_delta.setdefault(uid, m.offset)
        elif event == "finish":
            finish_at[uid] = m.offset
    streamed = [u for u, toks in done.items() if toks]
    assert all(first_delta[u] < finish_at[u] for u in streamed), \
        "deltas must precede completion on the bus"
    print(f"streaming: {sum(len(t) for t in done.values())} deltas published "
          f"before {len(finish_at)} completions")
    return 0


def _run_fleet(args, bus, events, make_engine) -> int:
    """Supervised-fleet driver: FleetSupervisor + N engine workers with
    probes, crash-replay recovery and autoscaling (``serving/fleet.py``)."""
    from repro.serving.fleet import FleetConfig, FleetSupervisor

    fcfg = FleetConfig(
        workers=args.fleet,
        min_replicas=1,
        max_replicas=max(args.fleet, 4),
        target_lag_per_replica=args.max_batch * 2,
    )
    sup = FleetSupervisor(bus, make_engine, fcfg, events=events)
    expected = [f"r{i}" for i in range(args.requests)]
    t0 = time.time()
    ok = sup.run(expected=expected, timeout_s=600)
    wall = time.time() - t0
    sup.shutdown()
    states = sup.results()
    n_tokens = sum(len(s.tokens) for s in states.values())
    print(f"fleet served {len(states)}/{args.requests} requests in "
          f"{wall:.1f}s ({n_tokens / wall:.1f} tok/s), "
          f"workers={args.fleet}+auto, "
          f"supervision: {sup.metrics.format()}")
    autoscales = events.history("autoscale")
    print("autoscale events:", [(e["old"], e["new"]) for e in autoscales])
    assert ok, "fleet run timed out with requests still in flight"

    # same streaming invariant as the flat pool: every streamed request's
    # first delta precedes its terminal finish on the responses topic
    first_delta: dict[str, int] = {}
    finish_at: dict[str, int] = {}
    for m in bus.read("responses"):
        uid, event = m.value["uid"], m.value["event"]
        if event == "delta":
            first_delta.setdefault(uid, m.offset)
        elif event == "finish":
            finish_at[uid] = m.offset
    streamed = [u for u, s in states.items() if s.tokens]
    assert all(first_delta[u] < finish_at[u] for u in streamed), \
        "deltas must precede completion on the bus"
    print(f"streaming: {n_tokens} deltas published before "
          f"{len(finish_at)} completions")
    return 0


def _run_worker(args, bus, make_engine) -> int:
    """Standalone fleet worker: the multi-process deployment shape. A
    supervisor in another process (same ``--workdir`` bus) publishes
    ``fleet.work``; this process serves it until the topic drains."""
    from repro.serving.fleet import (
        EngineWorker,
        FleetConfig,
        WORK_TOPIC,
        WORKER_GROUP,
    )

    w = EngineWorker(args.worker_name, 0, bus, make_engine,
                     threading.Lock(), FleetConfig(workers=1, autoscale=False))
    w.start()
    idle_since = None
    while w.thread.is_alive():
        busy = bus.lag(WORK_TOPIC, WORKER_GROUP) > 0 or w.inflight
        idle_since = None if busy else (idle_since or time.time())
        if idle_since is not None and time.time() - idle_since > 2.0:
            w.retire()
            break
        time.sleep(0.05)
    w.thread.join(timeout=30)
    print(f"worker {w.pod_id}: steps={w.steps_run} "
          f"tokens={w.tokens_emitted} clean={w.stopped_cleanly}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
