#!/usr/bin/env python3
"""Chip smoke: the serving path at published widths on a TPU, or nothing.

    python chip_smoke.py            # one chip: smollm-360m, mamba2-1.3b
    python chip_smoke.py --chips 4  # four chips: llama3-8b at tp=4 only

Each phase builds a model at its full published width in bf16, with
weights made from a seed (no download), created directly in their serving
shardings. It serves a few requests through the normal ``EngineCore`` API
(``submit``/``step``) twice over the same weights and prompts: once with
the Pallas kernels (``"pallas"``) and once with the XLA reference lowering
(``"xla_chunked"``). Eight prompts of 64-200 tokens share one 48-token
prefix and ask for 16 greedy tokens each, so chunked prefill, shared
prefix pages, the fused mixed step and plain decode steps all run.

A phase fails unless:

* every request of both engines finishes with its 16 tokens;
* the compiled fused step of the kernel engine holds the Pallas kernels
  (``tpu_custom_call`` in its compiled text), so a silent lowering to XLA
  fails the smoke;
* at the first generated position, and at the position after one decode
  step, the kernel path's logits agree with the reference path's within
  the bf16 tolerance below.

The tolerance is calibrated on the chip, per phase. The same weights and
prompts also run through the reference lowering with float32 activations
at the highest matmul precision. The bf16 reference path's distance from
that float32 run, ``drift`` (max |Δ| over max |logit|), is what bf16
rounding alone costs this model on these prompts. Two bf16 evaluations
that are each within ``drift`` of the exact logits are within
``2 * drift`` of each other, so the kernel path must agree with the bf16
reference within ``DRIFT_FACTOR * drift + DRIFT_FLOOR``; the floor (about
2.5 bf16 steps of 2^-8) covers a reference that happens to round very
close. A wrong mask, page or decay moves logits by the order of the logits
themselves. The check has power only while ``drift`` is small, so a phase
whose bf16 reference drifts more than ``MAX_DRIFT`` from float32 fails too.
The greedy streams of the two engines are printed with the first position
where they diverge: near-ties between the top two logits flip under bf16
rounding, so a divergence is reported, not failed.

The script runs in one process (a chip belongs to one process), exits
non-zero without a result line when JAX finds no TPU or when the ``repro``
package is not next to it, and prints as its last stdout line only
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
NUM_PROMPTS, SHARED_PREFIX, NEW_TOKENS = 8, 48, 16
PROMPT_LEN = (64, 200)
CHUNK, PAGE, SLOTS, MAX_LEN = 64, 16, 8, 256
DRIFT_FACTOR, DRIFT_FLOOR, MAX_DRIFT = 2.0, 1e-2, 0.1
KERNEL, REFERENCE = "pallas", "xla_chunked"


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def log(name: str, msg: str) -> None:
    print(f"[{name}] {msg}", flush=True)


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit counts its retrieval time)."""

    def __init__(self, jax) -> None:
        from jax._src import dispatch

        self.seconds = 0.0
        self.compiles = 0
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

    def snapshot(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


def make_prompts(vocab: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(SEED)
    shared = rng.integers(1, vocab, SHARED_PREFIX).tolist()
    lens = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, NUM_PROMPTS)
    return [shared + rng.integers(1, vocab, n - SHARED_PREFIX).tolist()
            for n in lens]


def describe(cfg, tp: int) -> str:
    if cfg.family == "ssm":
        shape = (f"ssm_heads={cfg.ssm_heads} head_dim={cfg.ssm_head_dim} "
                 f"state={cfg.ssm_state} d_inner={cfg.d_inner}")
    else:
        shape = (f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
                 f"head_dim={cfg.head_dim} d_ff={cfg.d_ff}")
    return (f"widths: layers={cfg.num_layers} d_model={cfg.d_model} {shape} "
            f"vocab={cfg.vocab_size} dtype={cfg.dtype} tp={tp} "
            f"params={cfg.param_count() / 1e9:.3f}B")


def serve(engine, prompts) -> tuple[list[list[int]], float, int]:
    """Submit every prompt, step the engine dry; returns the streams."""
    from repro.serving import FinishReason, Request

    handles = [engine.submit(Request(f"r{i}", p, max_new_tokens=NEW_TOKENS))
               for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    steps = 0
    while not engine.idle:
        engine.step()
        steps += 1
        if steps > 50 * NUM_PROMPTS * NEW_TOKENS:
            raise SmokeFailure(f"engine did not drain in {steps} steps")
    wall = time.perf_counter() - t0
    streams = []
    for h in handles:
        r = h.result()
        if r.finish_reason != FinishReason.LENGTH or len(r.tokens) != NEW_TOKENS:
            raise SmokeFailure(
                f"{h.uid}: finished {r.finish_reason} with {len(r.tokens)} "
                f"tokens ({r.error})")
        streams.append(list(r.tokens))
    return streams, wall, steps


def custom_calls(compiled_text: str) -> int:
    return compiled_text.count("tpu_custom_call")


def paged_logits(ex, model, params, dtype, prompts, next_tokens):
    """Logits at the first generated position (chunked prefill over fresh
    pages of ``dtype``) and one decode step later, feeding
    ``next_tokens``; ``model``/``params`` run under the paged executor
    ``ex``'s own mesh and shardings."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mp = ex.cache.block_tables.shape[1]
    row = jnp.arange(1, mp + 1, dtype=jnp.int32)  # fresh pages, 0 is null
    page_specs = ex._page_specs()

    def prefill(params, pages, tokens, start, valid):
        with ex._tp_ctx():
            return model.prefill_chunk(params, pages, row, tokens, start,
                                       valid)

    def decode(params, pages, token, length):
        with ex._tp_ctx():
            return model.decode_step_paged(
                params, pages, row[None], length[None], token[None, None])

    prefill = jax.jit(ex._smap(
        prefill, in_specs=(ex.param_specs, page_specs) + (P(),) * 3,
        out_specs=(page_specs, P())), donate_argnums=(1,))
    decode = jax.jit(ex._smap(
        decode, in_specs=(ex.param_specs, page_specs) + (P(),) * 2,
        out_specs=(page_specs, P())), donate_argnums=(1,))
    out = []
    for prompt, nxt in zip(prompts, next_tokens):
        pages = {k: jnp.zeros_like(v, dtype) for k, v in ex.cache.pages.items()}
        for start in range(0, len(prompt), CHUNK):
            piece = prompt[start:start + CHUNK]
            toks = jnp.asarray(piece + [0] * (CHUNK - len(piece)), jnp.int32)
            pages, first = prefill(params, pages, toks, jnp.int32(start),
                                   jnp.int32(len(piece)))
        _, second = decode(params, pages, jnp.int32(nxt),
                           jnp.int32(len(prompt)))
        out.append((first, second[0]))
    return out


def ssm_logits(ex, model, params, dtype, prompts, next_tokens):
    """The SSM executor's counterpart of :func:`paged_logits`: chunked SSD
    prefill from a zero slot state, then one recurrent decode step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.serving.ssm_engine import STATE_SPECS

    def prefill(params, state, tokens, valid):
        with ex._tp_ctx():
            return model.prefill_chunk_ssm(params, state, tokens, valid)

    def decode(params, state, token):
        with ex._tp_ctx():
            return model.decode_step_ssm(
                params, state, token[None, None], jnp.ones((1,), jnp.int32))

    prefill = jax.jit(ex._smap(
        prefill, in_specs=(ex.param_specs, STATE_SPECS) + (P(),) * 2,
        out_specs=(STATE_SPECS, P())), donate_argnums=(1,))
    decode = jax.jit(ex._smap(
        decode, in_specs=(ex.param_specs, STATE_SPECS, P()),
        out_specs=(STATE_SPECS, P())), donate_argnums=(1,))
    out = []
    for prompt, nxt in zip(prompts, next_tokens):
        # the SSD state is f32 in every model; the conv tails follow dtype
        state = {k: jnp.zeros_like(v[:, :1], jnp.float32 if k == "ssm" else dtype)
                 for k, v in ex.bank.state.items()}
        for start in range(0, len(prompt), CHUNK):
            piece = prompt[start:start + CHUNK]
            toks = jnp.asarray(piece + [0] * (CHUNK - len(piece)), jnp.int32)
            state, first = prefill(params, state, toks, jnp.int32(len(piece)))
        _, second = decode(params, state, jnp.int32(nxt))
        out.append((first, second[0]))
    return out


def probe(engine, prompts, next_tokens, *, float32: bool = False):
    """Logits of ``engine``'s lowering at the two checked positions; with
    ``float32`` the same weights run through the reference lowering with
    float32 activations at the highest matmul precision."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.models import build_model

    ex = engine.executor
    fn = ssm_logits if engine.cfg.family == "ssm" else paged_logits
    if not float32:
        return fn(ex, ex.model, ex.params, jnp.dtype(engine.cfg.dtype),
                  prompts, next_tokens)
    cfg32 = dataclasses.replace(engine.cfg, dtype="float32")
    model32 = build_model(cfg32, attn_impl=REFERENCE, ssd_impl=REFERENCE)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), ex.params)
    with jax.default_matmul_precision("highest"):
        return fn(ex, model32, params32, jnp.float32, prompts, next_tokens)


def fused_step_text(engine) -> str:
    """Compiled text of the engine's fused step: the mixed prefill+decode
    dispatch of the paged engine, the decode dispatch of the SSM engine
    (found again in the compile cache the serving run filled)."""
    import jax
    import jax.numpy as jnp

    ex = engine.executor
    if hasattr(ex, "_mixed_fn"):
        mp = ex.cache.block_tables.shape[1]
        ci = jax.ShapeDtypeStruct((mp + CHUNK + 4,), jnp.int32)
        cf = jax.ShapeDtypeStruct((2,), jnp.float32)
        lowered = ex._mixed_fn(True).lower(
            ex.params, dict(ex.cache.pages), ex._di, ex._df, ci, cf)
    else:
        lowered = ex._decode_fn(True).lower(
            ex.params, ex.bank.state, ex._di, ex._df)
    return lowered.compile().as_text()


def rel_err(got, want, vocab: int) -> float:
    """max |got - want| over max |want| on the real (unpadded) vocab."""
    import numpy as np

    g = np.asarray(got, np.float32)[:vocab]
    w = np.asarray(want, np.float32)[:vocab]
    if not (np.isfinite(g).all() and np.isfinite(w).all()):
        return float("inf")
    return float(np.abs(g - w).max() / np.abs(w).max())


def compare(name: str, vocab: int, kern, ref, exact) -> list[str]:
    """Hold the kernel path's logits to the calibrated bf16 tolerance;
    returns the failed checks."""
    import numpy as np

    problems = []
    for pos in (0, 1):
        kr = [rel_err(k[pos], r[pos], vocab) for k, r in zip(kern, ref)]
        drift = [rel_err(r[pos], e[pos], vocab) for r, e in zip(ref, exact)]
        kx = [rel_err(k[pos], e[pos], vocab) for k, e in zip(kern, exact)]
        top = sum(int(np.argmax(np.asarray(k[pos])[:vocab]))
                  == int(np.argmax(np.asarray(r[pos])[:vocab]))
                  for k, r in zip(kern, ref))
        tol = DRIFT_FACTOR * max(drift) + DRIFT_FLOOR
        log(name, f"logits position {pos}: kernel vs bf16 reference "
                  f"{max(kr):.3e} (tolerance {tol:.3e} = {DRIFT_FACTOR} x "
                  f"drift {max(drift):.3e} + {DRIFT_FLOOR}); vs float32: "
                  f"kernel {max(kx):.3e}, bf16 reference {max(drift):.3e}; "
                  f"top-1 agrees in {top}/{len(kern)} prompts")
        if max(drift) > MAX_DRIFT:
            problems.append(f"position {pos}: the bf16 reference drifts "
                            f"{max(drift):.3e} from float32 (> {MAX_DRIFT}); "
                            f"the comparison has no power")
        if not max(kr) <= tol:
            problems.append(f"position {pos}: kernel logits off the "
                            f"reference by {max(kr):.3e} > {tol:.3e}")
    return problems


def report_streams(name: str, kern, ref) -> None:
    for i, (a, b) in enumerate(zip(kern, ref)):
        div = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y), None)
        where = "identical" if div is None else f"diverge at position {div}"
        log(name, f"r{i} {where}: kernel {a}")
        if div is not None:
            log(name, f"r{i}{' ' * len(where)}  reference {b}")


def run_phase(name: str, meter, *, kernel: str = KERNEL, cfg=None) -> None:
    """Serve ``name`` with the kernel and reference lowerings and check
    them against each other. ``cfg`` overrides the published config (a
    cut-down rehearsal off the chip passes a reduced one together with
    ``kernel="pallas_interpret"``)."""
    import jax

    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.serving import ContinuousBatchingEngine, SSMEngine
    from repro.serving.executor import (
        default_serving_mesh,
        init_serving_params,
        validate_serving_mesh,
    )

    cfg = cfg or get_arch(name)
    mesh = default_serving_mesh(cfg)
    tp = validate_serving_mesh(cfg, mesh)
    log(name, describe(cfg, tp))
    c0 = meter.snapshot()
    t0 = time.perf_counter()
    params = init_serving_params(cfg, jax.random.key(SEED), mesh)
    jax.block_until_ready(params)
    log(name, f"sharded init: {time.perf_counter() - t0:.2f} s "
              f"(every leaf created in its NamedSharding on {mesh.devices.size} "
              f"device(s))")

    ssm = cfg.family == "ssm"
    op = "ssd_scan" if ssm else "paged_mixed_attention"
    resolved = ops._resolve_pallas_impl(kernel, False, op)
    log(name, f"impls: kernel engine {'ssd_impl' if ssm else 'attn_impl'}="
              f"{kernel} -> (impl, interpret)={resolved}; reference engine "
              f"{REFERENCE}; backend {jax.default_backend()}")

    def make(impl):
        if ssm:
            return SSMEngine(cfg, params, max_len=MAX_LEN, max_slots=SLOTS,
                             prefill_chunk=CHUNK, ssd_impl=impl)
        return ContinuousBatchingEngine(
            cfg, params, max_len=MAX_LEN, max_slots=SLOTS, page_size=PAGE,
            prefill_chunk=CHUNK, prefix_sharing=True, attn_impl=impl)

    prompts = make_prompts(cfg.vocab_size)
    log(name, f"prompts: {len(prompts)} of {[len(p) for p in prompts]} "
              f"tokens, shared prefix {SHARED_PREFIX}, {NEW_TOKENS} new "
              f"tokens each, chunk {CHUNK}")
    engines, streams, problems = {}, {}, []
    for impl in (kernel, REFERENCE):
        engines[impl] = engine = make(impl)
        streams[impl], wall, steps = serve(engine, prompts)
        served = sum(len(s) for s in streams[impl])
        log(name, f"served {impl}: {len(prompts)}/{len(prompts)} requests "
                  f"finished, {served} tokens in {steps} steps, "
                  f"{wall:.2f} s wall on the host clock (compiles "
                  f"included); stats {engine.stats}")
        n_calls = custom_calls(fused_step_text(engine))
        log(name, f"fused step ({impl}): tpu_custom_call x {n_calls} in "
                  f"the compiled text")
        if impl == KERNEL and not n_calls:
            problems.append("the compiled fused step holds no Pallas "
                            "kernel: it lowered to XLA")
    report_streams(name, streams[kernel], streams[REFERENCE])
    # every lowering is fed the reference's first token, so position 1
    # compares the same context even where the streams diverge
    nxt = [s[0] for s in streams[REFERENCE]]
    kern = probe(engines[kernel], prompts, nxt)
    ref = probe(engines[REFERENCE], prompts, nxt)
    exact = probe(engines[REFERENCE], prompts, nxt, float32=True)
    problems += compare(name, cfg.vocab_size, kern, ref, exact)
    c1 = meter.snapshot()
    log(name, f"compile: {c1[0] - c0[0]:.2f} s in {c1[1] - c0[1]} "
              f"compiles, {c1[2] - c0[2]} persistent-cache hits")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in mesh.devices.flat)
    log(name, f"peak_bytes_in_use={peak} ({peak / 2**30:.2f} GiB, "
              f"largest over the mesh's devices, process-wide peak)")
    if problems:
        raise SmokeFailure("; ".join(problems))
    log(name, "PASS")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: smollm-360m and mamba2-1.3b on one chip; "
                         "4: llama3-8b tensor-parallel over four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX finds {len(devices)} "
              f"{devices[0].platform!r} device(s) and no accelerator; this "
              f"smoke runs on a TPU only and never falls back",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX finds {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"(looked in {ROOT / 'src'}): {e}", file=sys.stderr)
        return 2
    print(f"chip_smoke: compile cache at {enable_compile_cache()}")
    meter = CompileMeter(jax)
    d = devices[0]
    print(f"chip_smoke: {len(devices)} x {d.device_kind} ({d.platform}), "
          f"jax {jax.__version__}")

    phases = ["llama3-8b"] if args.chips == 4 else ["smollm-360m", "mamba2-1.3b"]
    failed = []
    for name in phases:
        try:
            run_phase(name, meter)
        except Exception:
            traceback.print_exc()
            log(name, "FAIL")
            failed.append(name)
        gc.collect()  # drop the phase's engines and weights before the next
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
