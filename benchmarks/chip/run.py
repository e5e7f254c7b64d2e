#!/usr/bin/env python3
"""One benchmark cell, run once on the chip:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is the ``workloads`` entry of ``BENCHMARK.json`` named
``<name>``: a configuration (``configs/<config>.json``: the model, its
published sizes, the engine's settings, the plain reference that checks
it) under a traffic mix (``traffic/<traffic>.json``, read by
``traffic.py``). Metrics are files too: ``metrics/<metric>.py``, each with
its name, unit, layer and a ``read(ctx)``; the run reports the cell's
``end_to_end`` metrics with ``--trace 0`` and its ``per_layer`` metrics with
``--trace 1``. Nothing here names a cell, a configuration or a metric.

A run, in one process on one chip:

1. set-up (``setup_s``, from process start to the first timed request):
   the program's package from the checkout's ``src``, JAX's persistent
   compilation cache at ``<checkout>/.jax_cache``, random weights made on
   the device from ``--seed`` (``weights.py``), the engine built through
   ``engines/<kind>.py``, a short pass that compiles or loads every
   program the traffic uses, then ``warm_s`` seconds of the mix itself so
   that the window opens on full caches and batches;
2. the window: ``--seconds`` of the mix, driven through
   ``EngineCore.submit()``/``step()``. Open-loop requests are timed from
   the time they were due, tokens on the benchmark's own clock as each
   ``step()`` returns them. With ``--trace 1`` the profiler records a
   slice of the window, with each step, each executor call and each
   generator action in ``bench.*`` spans (``devtrace.py`` reduces it);
3. the check (``correct``): a sample of finished requests drawn from the
   seed, the longest among them, is run through the configuration's plain
   float32 reference (``references/``) after the engine is freed. At each
   served position the gap by which the served token's logit lies below
   the reference's best, in units of that position's logit standard
   deviation, is read; the widest gap (``max_gap``) and the mean over all
   served positions (``mean_gap``) must each stay within the limit the
   configuration's ``check`` gives it.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``check``, the numbers compared with their limits,
which are also the last lines on standard error. Without a TPU, with fewer
chips than the cell asks for, or without the program next to the
benchmark, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import heapq  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import traffic as traffic_mod  # noqa: E402

# seconds past the window's close that a due request may take to start
LATE_S = 60.0


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def find_cell(bench: dict, workload: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return Cell(
        name=workload, chips=int(w["chips"]),
        conf=load_json(HERE / "configs" / f"{w['config']}.json"),
        mix=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        end_to_end=mine(bench["end_to_end"]),
        per_layer=mine(bench["per_layer"]),
    )


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self, jax) -> None:
        from jax._src import dispatch

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self._event:
            self.seconds += duration
            self.compiles += 1

    def _count(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


@dataclass
class Rec:
    """What the benchmark saw of one request, on its own clock."""

    item: traffic_mod.Item
    due: float                       # absolute perf_counter seconds
    submit: float | None = None
    first: float | None = None
    last: float | None = None
    done: float | None = None
    ok: bool | None = None
    tokens: list[int] = field(default_factory=list)
    handle: object = None


class Driver:
    """Offers the mix to the engine and stamps what comes back."""

    def __init__(self, engine, traffic, *, annotate):
        self.engine = engine
        self.traffic = traffic
        self.annotate = annotate
        self.heap: list = []
        self.recs: dict[str, Rec] = {}
        self.stamps: list[float] = []          # every token's time
        self.gaps: list[tuple[float, float]] = []  # (end time, gap s)
        self.preempted = 0
        self.origin = 0.0
        self.stop_due = float("inf")
        self._seq = 0

    def push(self, item) -> None:
        self._seq += 1
        heapq.heappush(self.heap, (self.origin + item.due, self._seq, item))

    def start(self, origin: float, stop_due: float) -> None:
        self.origin, self.stop_due = origin, stop_due
        for item in self.traffic.initial():
            self.push(item)

    def _submit_due(self, now: float) -> None:
        from repro.serving import FinishReason, Request, SamplingParams

        while self.heap and self.heap[0][0] <= now:
            due, _, item = heapq.heappop(self.heap)
            if due >= self.stop_due:
                continue
            rec = Rec(item, due)
            self.recs[item.uid] = rec
            rec.submit = time.perf_counter()
            rec.handle = self.engine.submit(Request(
                item.uid, item.prompt, arrival_t=due,
                sampling=SamplingParams(max_new_tokens=item.max_new)))
            if rec.handle.finish_reason is FinishReason.REJECTED:
                self._finish(rec, ok=False, t=rec.submit)

    def _finish(self, rec: Rec, ok: bool, t: float) -> None:
        rec.done, rec.ok = t, ok
        rec.tokens = list(rec.handle.tokens)
        for nxt in self.traffic.on_finish(rec.item, rec.tokens, ok,
                                          t - self.origin):
            self.push(nxt)

    def pump(self, until, hooks=()) -> None:
        """Run until ``until(now)`` says stop; ``hooks`` are called with
        the time before every step (trace start and stop)."""
        from repro.serving import FinishReason

        engine = self.engine
        while True:
            now = time.perf_counter()
            for h in hooks:
                h(now)
            if until(now):
                return
            with self.annotate("bench.generate"):
                self._submit_due(now)
            if engine.idle:
                nxt = self.heap[0][0] if self.heap else now + 0.01
                with self.annotate("bench.idle"):
                    time.sleep(min(max(nxt - time.perf_counter(), 0.0), 0.01))
                continue
            with self.annotate("bench.step"):
                events = engine.step()
            t = time.perf_counter()
            for ev in events:
                rec = self.recs[ev.uid]
                if ev.kind == "token":
                    self.stamps.append(t)
                    if rec.first is None:
                        rec.first = t
                    else:
                        self.gaps.append((t, t - rec.last))
                    rec.last = t
                elif ev.kind == "finish":
                    ok = ev.finish_reason in (FinishReason.LENGTH,
                                              FinishReason.STOP)
                    self._finish(rec, ok, t)
                else:
                    self.preempted += 1


def warm_compile(engine, vocab: int, chunk: int, page: int) -> None:
    """Run every program the mix will use once: prefill chunks with
    nothing decoding, a chunk beside a decoding sequence (the fused mixed
    step), a shared prefix, and plain decode steps."""
    from repro.serving import Request, SamplingParams

    rng = np.random.default_rng(7)
    a = rng.integers(1, vocab, 2 * chunk + page + 3).tolist()
    b = a[:chunk + page] + rng.integers(1, vocab, chunk).tolist()
    engine.submit(Request("warm-a", a,
                          sampling=SamplingParams(max_new_tokens=24)))
    while not any(e.kind == "token" for e in engine.step()):
        pass
    engine.submit(Request("warm-b", b,
                          sampling=SamplingParams(max_new_tokens=4)))
    while not engine.idle:
        engine.step()


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


@dataclass
class Ctx:
    """What a metric's ``read(ctx)`` may look at."""

    loop: str
    kind: str                # the engine: "paged" or "ssm"
    seconds: float
    dm: dict
    peak: dict
    origin: float
    window_end: float
    setup_s: float
    end_wait: float          # when the run stopped waiting for late starts
    recs: list[Rec]          # requests due in the window
    stamps: list[float]
    gaps: list[tuple[float, float]]
    c0: dict
    c1: dict
    slot_samples: list[float]
    stalls: list = field(default_factory=list)  # profiler start/stop calls
    trace: object = None     # devtrace.Trace of the traced slice
    summary: object = None   # devtrace.Summary of it
    exec_log: list = field(default_factory=list)  # executor calls in it


def model_config(conf: dict):
    """The program's model config with every size the configuration file
    maps to it (``program_keys``) set from the file: the published
    ``config``, and ``as_run`` where the published code derives the size
    it runs (a padded vocabulary)."""
    import dataclasses

    from repro.configs import get_arch

    sizes = dict(conf["config"], **conf.get("as_run", {}))
    return dataclasses.replace(get_arch(conf["model"]), **{
        attr: sizes[k] for k, attr in conf["program_keys"].items()})


def pick_sample(finished: list[Rec], n: int, seed: int) -> list[Rec]:
    """``n`` finished requests drawn from the seed, the longest among
    them."""
    if not finished:
        return []
    finished = sorted(finished, key=lambda r: r.item.uid)
    longest = max(finished, key=lambda r: len(r.item.prompt) + len(r.tokens))
    rest = [r for r in finished if r is not longest]
    rng = np.random.default_rng([int(seed) % 2**63, 9])
    k = min(n - 1, len(rest))
    picks = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in sorted(picks)]


def check_numbers(gaps: list[np.ndarray], limits: dict) -> dict:
    """The numbers compared, each beside its limit, from the per-position
    gaps of the checked requests: the widest gap and the mean gap over
    every served position. With no position to read, each is None."""
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    values = {"max_gap": float(allg.max()) if allg.size else None,
              "mean_gap": float(allg.mean()) if allg.size else None}
    return {k: {"value": values[k], "limit": float(v)}
            for k, v in limits.items()}


def passes(check: dict) -> bool:
    """Correct: every number read, and none above its limit (no finished
    request to compare is no evidence)."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in check.values())


def gaps_of(ref, params, dm: dict, prompt: list[int], served: list[int],
            quant: str | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Per served position: the served token's gap below the reference's
    best logit, and (with ``quant``) the gap of the token the control puts
    first; both in units of the position's reference logit std."""
    first = len(prompt) - 1
    toks = list(prompt) + list(served)
    exact = np.asarray(ref.logits(params, toks, first, len(served), dm))
    std = exact.std(axis=1)
    best = exact.max(axis=1)
    rows = np.arange(len(served))
    served_gap = (best - exact[rows, np.asarray(served)]) / std
    if quant is None:
        return served_gap, None
    low = np.asarray(ref.logits(params, toks, first, len(served), dm, quant))
    pick = low.argmax(axis=1)
    return served_gap, (best - exact[rows, pick]) / std


def _mean_pct(samples) -> str:
    return f"{100 * float(np.mean(samples)):.1f}%" if samples else "n/a"


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             cfg=None, impl: str | None = None, trace_dir: str | None = None,
             controls: tuple[str, ...] = (), sabotage=None) -> dict:
    """Run one cell once and return the result line's object. ``cfg`` and
    ``impl`` replace the configuration's model and kernels (a CPU
    rehearsal at a reduced size); ``controls`` also reads the control
    precisions' gaps on the checked requests; ``sabotage(engine)`` breaks
    the timed path (the check's own tests)."""
    import jax

    from repro.models import build_model

    import devtrace

    conf, mix = cell.conf, cell.mix
    econf = conf["engine"]
    engines = load_module(HERE / "engines" / f"{econf['kind']}.py",
                          f"engine_{econf['kind']}")
    ref = load_module(HERE / "references" / f"{conf['reference']}.py",
                      f"reference_{conf['reference']}")
    if cfg is None:
        cfg = model_config(conf)
        dm = ref.dims(conf)
    else:
        dm = conf["dims"]
    meter = CompileMeter(jax)
    dev = jax.devices()[0]
    peaks = load_json(HERE / "peaks.json")["devices"]
    peak = peaks.get(dev.device_kind)
    if peak is None and impl is None:
        raise KeyError(f"no peaks for device kind {dev.device_kind!r} in "
                       f"peaks.json")

    # -- set-up --------------------------------------------------------------
    from weights import make_weights

    t = time.perf_counter()
    params = make_weights(build_model(cfg).abstract(), seed, conf.get("init"))
    jax.block_until_ready(params)
    log(f"set-up: weights {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    engine = engines.build(cfg, params, econf, impl or econf["impl"])
    if sabotage is not None:
        sabotage(engine)
    log(f"set-up: engine {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    warm_compile(engine, cfg.vocab_size, econf["prefill_chunk"],
                 econf.get("page_size", 16))
    log(f"set-up: compile pass {time.perf_counter() - t:.3f} s, "
        f"{meter.compiles} compiles ({meter.seconds:.3f} s), "
        f"{meter.cache_hits} persistent-cache hits")

    exec_log: list = []
    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: nullcontext()))
    if trace:
        engines.watch(engine, exec_log, annotate)
        # the device tracer's first work after it starts can stall for
        # seconds (one traced run of 4 s read a 3.6 s gap): a small op,
        # compiled here and run right after the start, takes that stall
        # outside the traced spans
        ping = jax.jit(lambda x: x + 1)
        one = jax.block_until_ready(ping(jax.numpy.zeros((8, 128))))
    traffic = traffic_mod.Traffic(mix, seed=seed, vocab=cfg.vocab_size,
                                  seconds=seconds, slots=econf["max_slots"],
                                  max_len=econf["max_len"])
    drv = Driver(engine, traffic, annotate=annotate)
    origin = time.perf_counter() + traffic.warm
    window_end = origin + seconds
    drv.start(origin, window_end)
    drv.pump(lambda now: now >= origin)
    setup_s = time.perf_counter() - T_START
    c0 = engines.counters(engine)
    queued0 = len(engine.admission)
    compiles0 = meter.compiles
    jax.config.update("jax_log_compiles", True)  # name any compile in it

    # -- window ----------------------------------------------------------------
    tdir = None
    tstate = {"on": False, "t": None}
    stalls: list[tuple[float, float]] = []  # the profiler's start and stop
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        t_on = origin + float(mix.get("trace_at_s", 1.0))
        t_off = min(t_on + float(mix.get("trace_s", 4.0)), window_end)

        def hook(now):
            if not tstate["on"] and tstate["t"] is None and now >= t_on:
                jax.profiler.start_trace(tdir)
                jax.block_until_ready(ping(one))
                started = time.perf_counter()
                stalls.append((now, started))
                tstate.update(on=True, t=(started, None))
            elif tstate["on"] and now >= t_off:
                stop = time.perf_counter()
                jax.profiler.stop_trace()
                stalls.append((stop, time.perf_counter()))
                tstate.update(on=False, t=(tstate["t"][0], stop))
        hooks = (hook,)
    else:
        hooks = ()
    drv.pump(lambda now: now >= window_end, hooks)
    c1 = engines.counters(engine)
    queued1 = len(engine.admission)
    compiles_in_window = meter.compiles - compiles0
    jax.config.update("jax_log_compiles", False)
    if tstate["on"]:
        hooks[0](float("inf"))
    window = [r for r in drv.recs.values() if origin <= r.due < window_end]
    open_loop = mix["loop"] == "open"
    if open_loop:
        # every request due in the window gets its first token, or a minute
        drv.pump(lambda now: now > window_end + LATE_S or all(
            r.first is not None or r.done is not None for r in window))
    end_wait = time.perf_counter()
    engine.abort_all()
    util = engine.utilization
    slot_samples = util.slot_samples[c0["slot_samples"]:c1["slot_samples"]]
    page_samples = util.page_samples[c0["page_samples"]:c1["page_samples"]]
    done_in = sum(1 for r in drv.recs.values()
                  if r.done is not None and origin <= r.done < window_end)
    log(f"window: {len(window)} requests due, {done_in} finished, queue "
        f"{queued0} -> {queued1} waiting, {len(drv.stamps)} tokens stamped "
        f"in the run, {compiles_in_window} compiles in the window, "
        f"{drv.preempted} preemptions, setup_s {setup_s:.3f}")
    log(f"window fill: decoding slots {_mean_pct(slot_samples)} of "
        f"{econf['max_slots']}, page pool {_mean_pct(page_samples)} in use "
        f"(means over the window's decode steps)")
    if compiles_in_window:
        log(f"warning: {compiles_in_window} compiles inside the window")

    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.devices()[:cell.chips])

    ctx = Ctx(loop=mix["loop"], kind=econf["kind"], seconds=seconds, dm=dm,
              peak=peak or {}, origin=origin, window_end=window_end, setup_s=setup_s,
              end_wait=end_wait,
              recs=window, stamps=drv.stamps, gaps=drv.gaps, c0=c0, c1=c1,
              slot_samples=slot_samples, stalls=stalls)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak_bytes)}
    result: dict = {}
    if trace:
        tr = devtrace.load(devtrace.find_xplane(tdir))
        t_lo, t_hi = tstate["t"]
        ctx.exec_log = [r for r in exec_log
                        if r["t"][0] >= t_lo and r["t"][1] <= t_hi]
        if tr.ops:  # a CPU run has no device plane: no device metric
            sm = devtrace.summarize(tr)
            ctx.trace, ctx.summary = tr, sm
            device["busy_s"] = sm.busy_ns / 1e9
            device["window_s"] = sm.window_ns / 1e9
            result["breakdown"] = devtrace.breakdown(sm)
            if trace_dir:
                with open(os.path.join(trace_dir, "describe.txt"), "w") as f:
                    f.write(devtrace.describe(tr))
        if not trace_dir:
            shutil.rmtree(tdir, ignore_errors=True)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        mod = load_module(HERE / "metrics" / f"{m['name']}.py",
                          f"metric_{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # a request fails when it is refused or cut off; in an open loop also
    # when it never started within LATE_S of the window's close (a closed
    # loop's last requests are still running when the window closes)
    attempted = len(window)
    failed = sum(1 for r in window
                 if r.ok is False or (open_loop and r.first is None))

    # -- the check -------------------------------------------------------------
    finished = [r for r in drv.recs.values()
                if r.ok and r.done is not None and r.done >= origin
                and len(r.tokens) == r.item.max_new]
    sample = pick_sample(finished, int(mix.get("check", {}).get("requests", 6)),
                         seed)
    checked = [(list(r.item.prompt), list(r.tokens)) for r in sample]
    del drv, engine, traffic, window, sample, finished, ctx
    gc.collect()
    t = time.perf_counter()
    gaps, ctrl = [], {q: [] for q in controls}
    for prompt, served in checked:
        g, _ = gaps_of(ref, params, dm, prompt, served)
        gaps.append(g)
        for q in controls:
            ctrl[q].append(gaps_of(ref, params, dm, prompt, served, q)[1])
    n_tok = sum(len(s) for _, s in checked)
    limits = conf["check"]
    log(f"check: {len(checked)} requests, {n_tok} served tokens against "
        f"the float32 reference in {time.perf_counter() - t:.3f} s")
    check = check_numbers(gaps, limits)
    for q, gs in ctrl.items():
        # the control put in the program's place, judged as the program is
        c = check_numbers(gs, limits)
        result.setdefault("controls", {})[q] = {"correct": passes(c),
                                                **c}
    result.update({
        "correct": passes(check), "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device, "check": check,
    })
    return result


def order_result(result: dict) -> dict:
    keys = ["correct", "attempted", "failed", "metrics", "device",
            "breakdown", "controls", "check"]
    return {k: result[k] for k in keys if k in result}


def prepare(cell: Cell) -> bool:
    """JAX with the checkout's compilation cache, a TPU with the chips the
    cell asks for, and the program's package from the checkout; False,
    with the reason on standard error, where one is missing."""
    # the cache lives in the checkout at a fixed path, whatever the
    # environment says: two checkouts never share compiled programs
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run: the cell needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return False
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"run: the program is not in this checkout ({ROOT / 'src'}): "
              f"{e}", file=sys.stderr)
        return False
    return True


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the trace and a readable look at it here")
    ap.add_argument("--rate", type=float, default=None,
                    help="offer this rate instead of the mix's (a knee sweep)")
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    if args.rate is not None:
        key = "session_rate" if cell.mix["kind"] == "sessions" else "rate"
        cell.mix[key] = args.rate
    if not prepare(cell):
        return 2
    result = order_result(run_cell(cell, seed=args.seed, seconds=args.seconds,
                                   trace=bool(args.trace),
                                   trace_dir=args.trace_dir))
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
