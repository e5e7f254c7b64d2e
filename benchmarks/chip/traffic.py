"""Seeded traffic: one general generator over the mixes in ``traffic/*.json``.

A mix is a data file. Its ``kind`` picks one of two shapes of traffic and its
``loop`` how it is offered:

* ``kind: "requests"``: independent requests, each a prompt and an output
  length drawn from clipped lognormals (``prompt_tokens``,
  ``output_tokens``: ``min``, ``max``, ``median``, ``sigma``).
  - ``loop: "open"``: Poisson arrivals at ``rate`` requests/s, optionally
    on/off modulated (``burst``: ``period_s``, ``on_s``, ``factor`` and
    ``at_s``, the burst's offset in each period). The number of arrivals in
    every phase is fixed by the rate, and their times within the phase
    are uniform: a Poisson process conditioned on its count.
  - ``loop: "closed"``: ``clients_per_slot`` x slots clients, each sending
    its next request as soon as the previous one finished. The requests
    are taken in one fixed order of sizes: a window of a closed loop
    finishes only a few tens of requests, so its throughput depends on
    which lengths come first, and a shuffle per seed moved it by a third.
* ``kind: "sessions"``, ``loop: "open"``: multi-turn chat sessions arriving
  at ``session_rate`` per second. Each opens with one of
  ``system_prompts.count`` shared system prompts picked with Zipf
  popularity, then runs ``turns`` turns; a turn sends the whole history
  (system prompt, earlier user turns and the served answers) plus a new user
  turn, and the next turn is due ``think_s`` (exponential mean) after the
  answer finished. A session ends before its context would pass
  ``max_context``.

What a seed changes and what it does not: the multiset of sizes (lengths,
turn counts, think times) and the arrival times are drawn from the mix's
own ``shape_seed``, so every run seed gets the same work at the same times;
the run seed shuffles which open-loop request or session gets which size,
and draws every token id. The same seed gives the same inputs.

A mix file may carry a ``source`` object, which the generator does not
read: where each parameter comes from (a published trace statistic, or an
assumption and why).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

# sizes drawn per mix for closed loops: far more than a window consumes
CLOSED_POOL = 8192


@dataclass
class Item:
    """One request as the generator hands it to the driver."""

    uid: str
    prompt: list[int]
    max_new: int
    due: float
    # sessions: (session index, turn index); closed loops: client index
    owner: tuple | int | None = None


def lognormal_sizes(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    z = rng.standard_normal(n)
    v = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def _rng(seed: int, *tag: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**63, *tag])


class Traffic:
    """Arrival schedule and follow-ups of one mix for one run seed.

    The driver calls :meth:`initial` once for the arrivals known up front,
    and :meth:`on_finish` whenever a request ends, for the requests it
    causes (a session's next turn, a closed-loop client's next request).
    Times are seconds relative to the start of the measured window; warm-up
    traffic has negative due times.
    """

    def __init__(self, mix: dict, *, seed: int, vocab: int, seconds: float,
                 slots: int, max_len: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.seconds = float(seconds)
        self.warm = float(mix["warm_s"])
        self.slots = slots
        self.max_len = max_len
        self.shape = np.random.default_rng(mix["shape_seed"])
        self.order = _rng(seed, 0)
        if mix["kind"] == "sessions":
            self._init_sessions()
        elif mix["loop"] == "closed":
            self._init_closed()
        else:
            self._init_open()

    # -- token ids ---------------------------------------------------------
    def _tokens(self, n: int, *tag: int) -> list[int]:
        return _rng(self.seed, 1, *tag).integers(1, self.vocab, n).tolist()

    # -- open loop of independent requests -----------------------------------
    def _arrival_times(self, rate: float) -> np.ndarray:
        """Arrival times over [-warm, seconds) with a fixed count per phase."""
        burst = self.mix.get("burst")
        rng = _rng(self.mix["shape_seed"], 2)
        out = []
        if not burst:
            for lo, hi in ((-self.warm, 0.0), (0.0, self.seconds)):
                n = int(round(rate * (hi - lo)))
                out.append(rng.uniform(lo, hi, n))
            return np.sort(np.concatenate(out))
        period, on, factor = burst["period_s"], burst["on_s"], burst["factor"]
        at = burst.get("at_s", 0.0)
        n_on = int(round(factor * rate * on))
        n_off = max(int(round(rate * period)) - n_on, 0)
        start = -math.ceil(self.warm / period) * period
        t = start
        while t < self.seconds:
            b0 = t + at
            out.append(rng.uniform(b0, b0 + on, n_on))
            off = rng.uniform(0.0, period - on, n_off)
            out.append(np.where(off < at, t + off, t + off + on))
            t += period
        times = np.sort(np.concatenate(out))
        return times[(times >= -self.warm) & (times < self.seconds)]

    def _init_open(self) -> None:
        times = self._arrival_times(self.mix["rate"])
        n = len(times)
        plen = lognormal_sizes(self.shape, self.mix["prompt_tokens"], n)
        olen = lognormal_sizes(self.shape, self.mix["output_tokens"], n)
        perm = self.order.permutation(n)
        self._open = [
            Item(f"r{i}", self._tokens(int(plen[j]), 0, i), int(olen[j]),
                 float(times[i]))
            for i, j in enumerate(perm)
        ]

    # -- closed loop ---------------------------------------------------------
    def _init_closed(self) -> None:
        self.clients = int(self.mix["clients_per_slot"] * self.slots)
        self._plen = lognormal_sizes(self.shape, self.mix["prompt_tokens"],
                                     CLOSED_POOL)
        self._olen = lognormal_sizes(self.shape, self.mix["output_tokens"],
                                     CLOSED_POOL)
        self._next = 0

    def _closed_item(self, client: int, due: float) -> Item:
        i = self._next
        self._next += 1
        j = i % CLOSED_POOL
        return Item(f"c{i}", self._tokens(int(self._plen[j]), 0, i),
                    int(self._olen[j]), due, owner=client)

    # -- sessions --------------------------------------------------------------
    def _init_sessions(self) -> None:
        m = self.mix
        sp = m["system_prompts"]
        k = sp["count"]
        self.sys_prompts = [
            self._tokens(int(n), 3, i) for i, n in enumerate(
                self.shape.integers(sp["min"], sp["max"] + 1, k))
        ]
        pop = 1.0 / np.arange(1, k + 1) ** sp["zipf"]
        pop /= pop.sum()
        rate = m["session_rate"]
        n_warm = int(round(rate * self.warm))
        n_win = int(round(rate * self.seconds))
        rng = _rng(m["shape_seed"], 2)
        starts = np.sort(np.concatenate([
            rng.uniform(-self.warm, 0.0, n_warm),
            rng.uniform(0.0, self.seconds, n_win)]))
        n = len(starts)
        shapes = []
        for _ in range(n):
            turns = int(self.shape.integers(m["turns"]["min"],
                                            m["turns"]["max"] + 1))
            shapes.append({
                "sys": int(self.shape.choice(k, p=pop)),
                "user": lognormal_sizes(self.shape, m["user_tokens"], turns),
                "out": lognormal_sizes(self.shape, m["output_tokens"], turns),
                "think": self.shape.exponential(m["think_s"], turns),
            })
        perm = self.order.permutation(n)
        self.sessions = [shapes[j] for j in perm]
        self.session_starts = starts
        self.history: dict[int, list[int]] = {}

    def _turn(self, s: int, turn: int, due: float) -> Item | None:
        shape = self.sessions[s]
        if turn >= len(shape["user"]):
            return None
        hist = self.history.setdefault(s, list(self.sys_prompts[shape["sys"]]))
        user = self._tokens(int(shape["user"][turn]), 4, s, turn)
        prompt = hist + user
        max_new = int(shape["out"][turn])
        limit = min(self.mix["max_context"], self.max_len)
        if len(prompt) + max_new > limit:
            return None
        return Item(f"s{s}t{turn}", prompt, max_new, due, owner=(s, turn))

    # -- driver interface ------------------------------------------------------
    def initial(self) -> list[Item]:
        if self.mix["kind"] == "sessions":
            items = [self._turn(s, 0, float(t))
                     for s, t in enumerate(self.session_starts)]
            return [it for it in items if it is not None]
        if self.mix["loop"] == "closed":
            return [self._closed_item(c, -self.warm)
                    for c in range(self.clients)]
        return list(self._open)

    def on_finish(self, item: Item, served: list[int], ok: bool,
                  now: float) -> list[Item]:
        """Requests caused by ``item`` ending at ``now`` (window time)."""
        if self.mix["kind"] == "sessions":
            s, turn = item.owner
            if not ok:
                return []
            self.history[s] = item.prompt + list(served)
            think = float(self.sessions[s]["think"][turn])
            nxt = self._turn(s, turn + 1, now + think)
            return [nxt] if nxt is not None else []
        if self.mix["loop"] == "closed":
            return [self._closed_item(item.owner, now)]
        return []
