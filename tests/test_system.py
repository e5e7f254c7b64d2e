"""End-to-end behaviour of the full Jup2Kub system (paper pipeline).

The notebook -> split -> deploy -> schedule -> recover loop, and the
fault-tolerant training workflow with chaos injection — compressed versions
of examples/ so the suite stays fast.
"""

import argparse
import json
from pathlib import Path

import pytest

from repro.core import (
    ArtifactStore, Notebook, TopicBus, WorkflowScheduler, split_pipeline,
)
from repro.core.autoscaler import Autoscaler, AutoscalerConfig
from repro.core.faults import FaultInjector, KillRule
from repro.core.scheduler import RetryPolicy


SCI_NOTEBOOK = [
    "import math\nraw = [i * 0.5 for i in range(200)]",
    "clean = [v for v in raw if v % 7 != 0]",
    "# %%pipe\nstats = (sum(clean), len(clean))",
    "norm = [v / stats[0] for v in clean]",
    "report = ('mean', stats[0] / stats[1])",
]


def test_notebook_to_k8s_end_to_end(tmp_path):
    """The paper's full promise: linear notebook in, fault-tolerant
    distributed execution out, same results, k8s manifests rendered."""
    nb = Notebook.from_sources(SCI_NOTEBOOK, name="sci")
    linear = nb.run_linear()
    g = split_pipeline(nb)
    assert len(g.steps) >= 3  # actually distributed

    bus = TopicBus(tmp_path / "bus")
    store = ArtifactStore(tmp_path / "store")
    first = sorted(g.steps)[0]
    faults = FaultInjector([KillRule(step=first, after_s=0.0, times=1)])
    sched = WorkflowScheduler(
        g, bus, store, retry=RetryPolicy(max_attempts=4, backoff_s=0.01),
        fault_injector=faults)
    arts = sched.run(timeout_s=60)
    assert arts["report"] == linear["report"]

    from repro.core.deployer import DynamicPodDeployer, PodManager
    dep = DynamicPodDeployer(PodManager(g), out_dir=tmp_path / "k8s")
    specs = dep.deploy_all()
    assert len(list((tmp_path / "k8s").glob("*-deployment.yaml"))) == len(g.steps)
    roles = {s.role for s in specs}
    assert "producer" in roles and "consumer" in roles


@pytest.mark.slow
def test_fault_tolerant_training_with_chaos(tmp_path):
    """Chaos kills the train pod twice; checkpoint/restart must finish the
    run and the loss must improve (learnable synthetic corpus)."""
    from repro.launch.train import build_workflow

    args = argparse.Namespace(
        arch="smollm-360m", reduced=True, steps=30, batch=8, seq_len=32,
        ga=1, lr=3e-3, seed=0, ckpt_every=6,
    )
    workdir = tmp_path / "run"
    workdir.mkdir()
    graph = build_workflow(args, workdir)
    bus = TopicBus(tmp_path / "bus")
    store = ArtifactStore(tmp_path / "store")
    claim = store.claim("ckpt")
    faults = FaultInjector([KillRule(step="train", after_s=0.8, times=2)])
    sched = WorkflowScheduler(
        graph, bus, store, workflow="ft-train",
        retry=RetryPolicy(max_attempts=6, backoff_s=0.05),
        liveness_window_s=30.0, fault_injector=faults,
        claim_paths={"train": str(claim.path)},
    )
    arts = sched.run(timeout_s=600)
    rep = arts["report"]
    assert rep["improved"], rep
    # the train step was actually killed and retried
    kinds = [e["kind"] for e in sched.events.history()]
    assert kinds.count("step_retry_scheduled") >= 1
    # checkpoints exist in the claimed volume (PVC analogue)
    assert any(claim.path.glob("step_*/MANIFEST.json"))


def test_autoscaler_scales_with_lag(tmp_path):
    bus = TopicBus(tmp_path)
    scaler = Autoscaler(
        bus, "reqs", "g",
        AutoscalerConfig(min_replicas=1, max_replicas=4,
                         target_lag_per_replica=5, scale_down_grace_s=0.0))
    assert scaler.observe() == (1, False)
    for i in range(20):
        bus.publish("reqs", i)
    desired, changed = scaler.observe()
    assert (desired, changed) == (4, True)
    bus.commit("reqs", "g", 20)  # consumers caught up
    desired, changed = scaler.observe()
    assert desired == 1 and changed


def test_heartbeat_liveness_cycle(tmp_path):
    import time

    from repro.core.probes import HealthMonitor, HeartbeatWriter

    bus = TopicBus(tmp_path)
    mon = HealthMonitor(bus, liveness_window_s=0.2)
    hb = HeartbeatWriter(bus, "pod1")
    assert mon.status("pod1") == "unknown"
    hb.ready()
    hb.beat(progress=1)
    assert mon.status("pod1") == "live"
    time.sleep(0.3)
    assert mon.status("pod1") == "dead"
    assert mon.dead_pods() == ["pod1"]
    hb.beat(progress=2)
    assert mon.status("pod1") == "live"
    assert mon.progress("pod1") == 2


def test_serving_api_surface_matches_snapshot():
    """The public serving surface must match the reviewed snapshot
    (``tools/serving_api.txt``) — the kernel-dispatch rework must add ZERO
    drift, since ``attn_impl`` was already on the engine signature and the
    ops layer is not part of ``repro.serving``. Intentional changes go
    through ``tools/check_api.py --update`` in the same PR."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_api", Path(__file__).parent.parent / "tools" / "check_api.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.render() == mod.SNAPSHOT.read_text(), (
        "public serving surface drifted from tools/serving_api.txt; "
        "run tools/check_api.py --update and review the diff"
    )


def test_compile_cache_placed_from_env_or_fixed_in_repo(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache goes to the one fixed, git-ignored directory in the
    checkout."""
    import jax

    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert compile_cache.enable_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir == prev

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = Path(__file__).resolve().parent.parent
        want = str(repo / ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_serve_stops_at_once_when_a_worker_fails(tmp_path, monkeypatch):
    """A worker that raises (here: compiled Pallas asked for off-TPU) ends
    the serving run with an error instead of being replaced by workers
    that would fail the same way until the run's 600 s deadline."""
    import sys
    import time

    import jax

    from repro.kernels.ops import PallasUnavailableError
    from repro.launch import serve

    if jax.default_backend() == "tpu":
        pytest.skip("compiled Pallas only fails off-TPU")
    # placed from outside: the helper then sets no cache in this process
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "smollm-360m", "--reduced", "--requests", "4",
        "--max-new", "2", "--attn-impl", "pallas",
        "--workdir", str(tmp_path / "run"),
    ])
    t0 = time.time()
    with pytest.raises(RuntimeError, match="run stopped") as err:
        serve.main()
    assert isinstance(err.value.__cause__, PallasUnavailableError)
    assert time.time() - t0 < 120
