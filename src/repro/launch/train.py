"""Fault-tolerant training driver: the paper's pipeline, end to end.

The training run is expressed as a Jup2Kub workflow of four steps —

    prepare_data -> train (long-running, checkpointed) -> evaluate -> report

— scheduled by WorkflowScheduler with heartbeats, retries and (optionally)
chaos injection. The train step checkpoints every ``--ckpt-every`` steps and
resumes from the latest checkpoint after a pod death; the data pipeline
replays deterministically from the restored step.

CPU-runnable with reduced configs:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
      --steps 60 --chaos
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def build_workflow(args, workdir: Path):
    from repro.configs import get_arch, reduced
    from repro.core.dag import Step, StepGraph
    from repro.data import DataConfig, SyntheticCorpus
    from repro.models import build_model
    from repro.checkpoint import CheckpointManager
    from repro.train import AdamWConfig, init_train_state, make_train_step

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    opt_cfg = AdamWConfig(
        lr=args.lr, warmup_steps=10, decay_steps=max(args.steps * 4, 100),
        weight_decay=0.0, moment_dtype="float32",
    )

    # ---------------- step fns ----------------
    def prepare_data(inputs):
        dc = DataConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq_len,
            global_batch=args.batch, seed=args.seed,
            vision_tokens=cfg.num_frontend_tokens if cfg.family == "vlm" else 0,
            frames=cfg.is_encoder_decoder, d_model=cfg.d_model, dtype=cfg.dtype,
        )
        return {"data_config": dc}

    def train(inputs, ctx):
        dc = inputs["data_config"]
        corpus = SyntheticCorpus(dc)
        model = build_model(cfg)
        step_fn = jax.jit(
            make_train_step(model, opt_cfg, ga=args.ga), donate_argnums=(0,)
        )
        ckpt = CheckpointManager(ctx.claim_path or workdir / "ckpt", keep=2)

        start = ckpt.latest_step()
        if start is not None:
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                init_train_state(model, jax.random.key(args.seed), opt_cfg),
            )
            state, meta = ckpt.restore(like, step=start)
            state = jax.tree.map(jnp.asarray, state)
            losses = list(meta.get("losses", []))
            ctx.beat(progress=start, info="restored")
        else:
            state = init_train_state(model, jax.random.key(args.seed), opt_cfg)
            losses = []
            start = 0

        for i in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in corpus.batch_at(i).items()}
            state, metrics = step_fn(state, batch)
            losses.append(float(metrics["loss"]))
            ctx.beat(progress=i + 1, loss=losses[-1])  # liveness + kill point
            if (i + 1) % args.ckpt_every == 0 or i + 1 == args.steps:
                ckpt.save(i + 1, state, meta={"losses": losses}, sync=True)
        return {"losses": losses, "final_step": args.steps,
                "ckpt_dir": str(ckpt.root)}

    def evaluate(inputs, ctx):
        from repro.train.step import make_eval_step
        dc = inputs["data_config"]
        corpus = SyntheticCorpus(dc)
        model = build_model(cfg)
        ckpt = CheckpointManager(inputs["ckpt_dir"])
        tmpl = init_train_state(model, jax.random.key(args.seed), opt_cfg)
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tmpl)
        state, _ = ckpt.restore(like)
        eval_fn = jax.jit(make_eval_step(model))
        tot = 0.0
        n_eval = 4
        for i in range(n_eval):
            batch = {k: jnp.asarray(v) for k, v in corpus.batch_at(10_000 + i).items()}
            tot += float(eval_fn(jax.tree.map(jnp.asarray, state["params"]), batch)["loss"])
        return {"eval_loss": tot / n_eval}

    def report(inputs):
        losses = inputs["losses"]
        rep = {
            "arch": cfg.name,
            "steps": inputs["final_step"],
            "first_loss": losses[0],
            "last_loss": losses[-1],
            "eval_loss": inputs["eval_loss"],
            "improved": bool(losses[-1] < losses[0]),
        }
        (workdir / "report.json").write_text(json.dumps(rep, indent=1))
        return {"report": rep}

    steps = {
        "prepare_data": Step("prepare_data", fn=prepare_data,
                             reads=set(), writes={"data_config"}, replicas=1),
        "train": Step("train", fn=train, reads={"data_config"},
                      writes={"losses", "final_step", "ckpt_dir"},
                      long_running=True, max_attempts=6),
        "evaluate": Step("evaluate", fn=evaluate,
                         reads={"data_config", "ckpt_dir"},
                         writes={"eval_loss"}, replicas=2),
        "report": Step("report", fn=report,
                       reads={"losses", "final_step", "eval_loss"},
                       writes={"report"}, replicas=1),
    }
    edges = {
        ("prepare_data", "train"): {"data_config"},
        ("prepare_data", "evaluate"): {"data_config"},
        ("train", "evaluate"): {"ckpt_dir"},
        ("train", "report"): {"losses", "final_step"},
        ("evaluate", "report"): {"eval_loss"},
    }
    return StepGraph(steps=steps, edges=edges).validate()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ga", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--chaos", action="store_true",
                    help="kill the train pod twice mid-run; FT must recover")
    ap.add_argument("--workdir", default="experiments/train_run")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.core import ArtifactStore, TopicBus, WorkflowScheduler
    from repro.core.faults import FaultInjector, KillRule
    from repro.core.scheduler import RetryPolicy

    workdir = Path(args.workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    graph = build_workflow(args, workdir)
    bus = TopicBus(workdir / "bus")
    store = ArtifactStore(workdir / "store")

    faults = None
    if args.chaos:
        faults = FaultInjector(
            [KillRule(step="train", after_s=1.0, times=2)]
        )
    claim = store.claim("train-ckpt", tier="shared")
    sched = WorkflowScheduler(
        graph, bus, store,
        workflow=f"train-{args.arch}",
        retry=RetryPolicy(max_attempts=6, backoff_s=0.1),
        liveness_window_s=30.0,
        fault_injector=faults,
        claim_paths={"train": str(claim.path)},
    )
    t0 = time.time()
    arts = sched.run(timeout_s=3600)
    rep = arts["report"]
    print(json.dumps(rep, indent=1))
    print(f"wall: {time.time()-t0:.1f}s")
    kinds = [e["kind"] for e in sched.events.history()]
    print("events:", {k: kinds.count(k) for k in sorted(set(kinds))})
    assert rep["improved"], "training did not reduce loss"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
