"""The output check catches a broken timed path. Each fault a serving cell
can have is planted under the engine, the rest of the run is driven as on
the chip (the look for a chip is skipped), and ``correct`` must come out
false: a token altered where it is produced, a step that returns its state
unchanged, half of the batch left out. (A one-chip cell has no exchange
between chips to leave out.)"""

import jax.numpy as jnp
import pytest

import run
from reduced import run_reduced

CELLS = [w["name"] for w in
         run.load_json(run.ROOT / "BENCHMARK.json")["workloads"]]


def alter_tokens(engine):
    """Every other delivered token is replaced by its neighbour id."""
    inner = engine._deliver
    vocab = engine.cfg.vocab_size

    def deliver(h, tok, idx, now):
        if idx % 2:
            tok = (tok + 1) % vocab
        return inner(h, tok, idx, now)

    engine._deliver = deliver


def _state(engine):
    if hasattr(engine, "bank"):
        return engine.bank.state, lambda s: setattr(engine.bank, "state", s)
    return engine.cache.pages, engine.cache.swap_pages


def state_unchanged(engine):
    """Every step with decode rows hands back the state it was given."""
    ex = engine.executor
    name = "step" if hasattr(ex, "step") else "decode"
    inner = getattr(ex, name)

    def step(*a, **kw):
        state, put = _state(engine)
        kept = {k: jnp.copy(v) for k, v in state.items()}
        out = inner(*a, **kw)
        if name == "decode" or a[0].decode_slots:
            put(kept)
        return out

    setattr(ex, name, step)


def half_batch(engine):
    """The upper half of each step's decode rows is left out: those slots
    get back the token they fed in."""
    ex = engine.executor
    if hasattr(ex, "step"):
        inner = ex.step

        def step(plan):
            toks, ctok = inner(plan)
            if toks is not None:
                toks = toks.copy()
                rows = plan.decode_slots
                for s in rows[len(rows) // 2:]:
                    toks[s] = engine.scheduler.slots[s].tokens[-1]
            return toks, ctok

        ex.step = step
    else:
        inner = ex.decode

        def decode(inputs=None):
            toks = inner(inputs).copy()
            rows = sorted(s for s, q in engine.slots.items()
                          if q.phase == "decode")
            for s in rows[len(rows) // 2:]:
                toks[s] = engine.slots[s].tokens[-1]
            return toks

        ex.decode = decode


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", [alter_tokens, state_unchanged, half_batch],
                         ids=lambda f: f.__name__)
def test_fault_fails_the_check(workload, fault):
    res = run_reduced(workload, sabotage=fault)
    gap = res["check"]["max_gap"]
    assert res["correct"] is False, gap
    assert gap["value"] > gap["limit"]
