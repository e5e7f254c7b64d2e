"""A cell of ``BENCHMARK.json`` cut to a size the CPU runs in seconds: the
program's ``reduced()`` model (float32, two layers), a few slots, and the
cell's own mix with its lengths, rates and window scaled down. What is not
scaled is the path: the same driver, engine, check and metric files."""

from __future__ import annotations

import copy

import run
from repro.configs import get_arch, reduced


def _shrink(spec: dict, hi: int) -> None:
    spec.update(min=min(spec["min"], hi // 2), max=hi, median=hi // 2)


def reduced_cell(workload: str):
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.find_cell(bench, workload)
    cfg = reduced(get_arch(cell.conf["model"]))
    conf = copy.deepcopy(cell.conf)
    eng = conf["engine"]
    if eng["kind"] == "paged":
        eng.update(max_slots=4, max_len=256, page_size=8, prefill_chunk=32)
        conf["dims"] = {
            "layers": cfg.num_layers, "d": cfg.d_model, "heads": cfg.num_heads,
            "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
            "theta": cfg.rope_theta, "eps": cfg.norm_eps,
            "tied": cfg.tie_embeddings}
    else:
        eng.update(max_slots=4, max_len=512, prefill_chunk=32)
        conf["dims"] = {
            "layers": cfg.num_layers, "d": cfg.d_model,
            "d_inner": cfg.d_inner, "heads": cfg.ssm_heads,
            "head_dim": cfg.ssm_head_dim, "state": cfg.ssm_state,
            "conv": cfg.conv_width, "chunk": cfg.ssm_chunk,
            "vocab": cfg.vocab_size, "eps": cfg.norm_eps,
            "tied": cfg.tie_embeddings}
    mix = copy.deepcopy(cell.mix)
    mix.update(warm_s=1.0, trace_at_s=0.5, trace_s=1.0)
    if mix["kind"] == "sessions":
        mix["system_prompts"].update(min=40, max=64)
        _shrink(mix["user_tokens"], 24)
        _shrink(mix["output_tokens"], 12)
        mix.update(max_context=256, session_rate=2.0, think_s=0.2)
    else:
        _shrink(mix["prompt_tokens"], 80)
        _shrink(mix["output_tokens"], 12)
        if mix["loop"] == "open":
            mix["rate"] = 4.0
    cell.conf, cell.mix = conf, mix
    return cell, cfg


def run_reduced(workload: str, *, seed: int = 2**33 + 5, seconds: float = 2.0,
                trace: bool = False, **kw) -> dict:
    cell, cfg = reduced_cell(workload)
    return run.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                        cfg=cfg, impl="xla_chunked", **kw)
