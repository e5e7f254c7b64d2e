"""The generator: the same seed gives the same inputs; another seed the
same sizes and arrival times in another order."""

import json
from pathlib import Path

import numpy as np
import pytest

from traffic import Traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def make(name, seed):
    return Traffic(mix(name), seed=seed, vocab=1000, seconds=30.0, slots=8,
                   max_len=8448)


@pytest.mark.parametrize("name", ["chat-burst", "sessions"])
def test_open_loop_same_seed_same_inputs(name):
    a, b = make(name, 2**40 + 3).initial(), make(name, 2**40 + 3).initial()
    assert [(i.uid, i.prompt, i.max_new, i.due) for i in a] == \
           [(i.uid, i.prompt, i.max_new, i.due) for i in b]


def test_burst_counts_fixed_across_seeds():
    m = mix("chat-burst")
    counts = []
    for seed in (1, 2, 3):
        items = make("chat-burst", seed).initial()
        due = np.array([i.due for i in items])
        window = due[(due >= 0) & (due < 30.0)]
        counts.append(len(window))
        b = m["burst"]
        at = b.get("at_s", 0.0)
        on = ((window % b["period_s"]) >= at) & (
            (window % b["period_s"]) < at + b["on_s"])
        assert on.sum() == 3 * round(b["factor"] * m["rate"] * b["on_s"])
    assert len(set(counts)) == 1


@pytest.mark.parametrize("name", ["chat-burst", "sessions"])
def test_arrival_times_fixed_across_seeds(name):
    a, b = make(name, 2**40 + 5).initial(), make(name, 7).initial()
    assert [i.due for i in a] == [i.due for i in b]


def test_sizes_are_one_multiset_in_another_order():
    a = make("chat-burst", 5).initial()
    b = make("chat-burst", 6).initial()
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt) for i in b)
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]


def test_closed_loop_sizes_fixed_across_seeds():
    a, b = make("batch-unshared", 1), make("batch-unshared", 2)
    ia, ib = a.initial(), b.initial()
    assert [len(i.prompt) for i in ia] == [len(i.prompt) for i in ib]
    assert [i.max_new for i in ia] == [i.max_new for i in ib]
    assert [i.prompt for i in ia] != [i.prompt for i in ib]


def test_closed_loop_clients_and_follow_ups():
    t = make("code-batch", 9)
    first = t.initial()
    assert len(first) == 2 * 8
    nxt = t.on_finish(first[0], [1, 2], True, 3.5)
    assert len(nxt) == 1 and nxt[0].owner == first[0].owner
    assert nxt[0].due == 3.5
    assert 128 <= len(nxt[0].prompt) <= 8192


def test_sessions_resend_history():
    t = make("sessions", 4)
    s0 = min(t.initial(), key=lambda i: i.due)
    served = [7] * s0.max_new
    nxt = t.on_finish(s0, served, True, 1.0)
    if nxt:
        assert nxt[0].prompt[:len(s0.prompt) + len(served)] == s0.prompt + served
        assert nxt[0].due > 1.0
