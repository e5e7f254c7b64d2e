"""The slot-state engine (``SSMEngine``): SSD scan for chunked prefill, SSD
decode step over the state bank."""

from __future__ import annotations

import time


def build(cfg, params, econf: dict, impl: str):
    from repro.serving import SSMEngine

    return SSMEngine(
        cfg, params, max_len=econf["max_len"], max_slots=econf["max_slots"],
        prefill_chunk=econf["prefill_chunk"], ssd_impl=impl)


def counters(engine) -> dict:
    u = engine.utilization
    return {
        "prefill_rows": u.prefill_rows, "decode_rows": u.decode_rows,
        "padded_rows": u.padded_rows, "slot_samples": len(u.slot_samples),
        "page_samples": len(u.page_samples),
        "preemptions": engine.stats["preemptions"],
    }


def watch(engine, log: list, annotate) -> None:
    """Record the work of every executor call into ``log`` and wrap it in
    a ``bench.execute`` span: the decoding slots of a decode dispatch, the
    start and live rows of a prefill chunk."""
    ex = engine.executor
    decode, chunk = ex.decode, ex.prefill_chunk

    def timed(rec, fn, *a):
        with annotate("bench.execute"):
            t0 = time.perf_counter()
            out = fn(*a)
            rec["t"] = (t0, time.perf_counter())
        log.append(rec)
        return out

    def dec(inputs=None):
        n = sum(1 for q in engine.slots.values() if q.phase == "decode")
        return timed({"decode_active": n}, decode, inputs)

    def pre(slot, seq, tokens, start, valid):
        return timed({"chunk": (int(start), int(valid))}, chunk,
                     slot, seq, tokens, start, valid)

    ex.decode, ex.prefill_chunk = dec, pre
