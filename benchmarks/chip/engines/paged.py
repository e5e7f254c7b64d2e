"""The paged continuous-batching engine (``ContinuousBatchingEngine``):
fused mixed step, paged Pallas kernels, prefix pages."""

from __future__ import annotations

import time


def build(cfg, params, econf: dict, impl: str):
    from repro.serving import ContinuousBatchingEngine

    return ContinuousBatchingEngine(
        cfg, params, max_len=econf["max_len"], max_slots=econf["max_slots"],
        page_size=econf["page_size"], num_pages=econf.get("num_pages"),
        prefill_chunk=econf["prefill_chunk"],
        token_budget=econf.get("token_budget"),
        prefix_sharing=econf["prefix_sharing"], attn_impl=impl)


def counters(engine) -> dict:
    u = engine.utilization
    return {
        "prefix_tokens_reused": engine.cache.stats["prefix_tokens_reused"],
        "prefill_rows": u.prefill_rows, "decode_rows": u.decode_rows,
        "padded_rows": u.padded_rows, "slot_samples": len(u.slot_samples),
        "page_samples": len(u.page_samples),
        "preemptions": engine.stats["preemptions"],
    }


def watch(engine, log: list, annotate) -> None:
    """Record the work of every executor call into ``log`` and wrap it in
    a ``bench.execute`` span: decode rows with the context each attends
    (its cached length plus itself), and the prefill chunk's start and
    live rows."""
    ex = engine.executor
    cache = engine.cache
    inner = ex.step

    def step(plan):
        ctx = [int(cache.lengths[s]) + 1 for s in plan.decode_slots]
        chunk = plan.chunk
        rec = {"decode_ctx": ctx,
               "chunk": (int(chunk.start), int(chunk.valid)) if chunk else None}
        with annotate("bench.execute"):
            t0 = time.perf_counter()
            out = inner(plan)
            rec["t"] = (t0, time.perf_counter())
        log.append(rec)
        return out

    ex.step = step
