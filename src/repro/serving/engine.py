"""Generation engines: lockstep micro-batching and continuous batching.

Both engines implement the :class:`repro.serving.api.EngineCore` protocol —
``submit() -> RequestHandle``, ``step() -> list[StreamEvent]``,
``cancel(uid)``, ``abort_all()`` — over the shared lifecycle machinery in
:class:`repro.serving.api.EngineBase`, so the bus worker, benchmarks and the
workflow scheduler drive them identically. Sampling (per-request temperature
/ top-k / top-p / seed) runs through ONE fused sample step
(``models.common.sample_tokens``) keyed off ``(seed, token_index)``, so a
request's token stream is independent of batch placement and survives
preemption byte-for-byte.

``GenerationEngine`` is the original synchronous batcher kept as the serving
baseline (and for model families without a paged decode path): it adapts the
protocol by chunking its micro-batches into steps — one ``step()`` call
forms a padded micro-batch and prefills it, each further call runs one
decode step over the whole batch, and the batch retires when every row has
finished (rows that stop early are masked, not evicted).

``ContinuousBatchingEngine`` is the hot path, built from two layers
(see ``docs/serving.md`` for the full design):

* a host-side :class:`repro.serving.scheduler.Scheduler` — admission order,
  chunked-prefill interleaving, prefix-sharing deferral, preemption victim
  selection, page accounting and decode-batch assembly, all plain
  Python/numpy with no device dispatch;
* a device-side :class:`repro.serving.executor.ModelExecutor` — the jitted
  fused prefill/decode+sample steps, run under ``shard_map`` on a
  ``("model",)`` mesh with attention heads tensor-parallel and the KV page
  pool sharded along the head dimension (a 1-device mesh runs the same
  code path unsharded).

The engine itself is the thin protocol adapter wiring the two: it
translates scheduler decisions into lifecycle events and executor calls.
Admission order is pluggable (``admission=`` takes any
:class:`repro.serving.api.AdmissionPolicy`; FIFO by default). Preemption
under page-pool pressure requeues the youngest sequences transparently —
their already-streamed deltas are never re-emitted — unless
``max_preemptions`` is exceeded, in which case the request finishes with
``FinishReason.PREEMPTED``. Chunked prefill (``prefill_chunk=N``, the
default; ``None``/0 restores whole-prompt bucketed prefill, automatic for
vlm prompts) and copy-on-write prefix sharing (``prefix_sharing=True``)
behave exactly as before the split.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import build_model
from repro.models.common import sample_tokens
from repro.serving.api import (
    AdmissionPolicy,
    EngineBase,
    FinishReason,
    Request,
    RequestHandle,
    Result,
    StreamEvent,
    validate_request,
)
from repro.core.storage import ArtifactStore
from repro.serving.executor import ModelExecutor
from repro.serving.kv_cache import PagedKVCache, cdiv
from repro.serving.kv_tiers import KVTierManager
from repro.serving.metrics import UtilizationMetrics, phase
from repro.serving.scheduler import Scheduler, Sequence
from repro.serving.speculative import SPEC_MODES, build_proposer

__all__ = [
    "ContinuousBatchingEngine",
    "GenerationEngine",
    "Request",
    "Result",
]


@dataclass
class _Row:
    """One row of a lockstep micro-batch."""

    request: Request
    handle: RequestHandle
    done: bool = False


class GenerationEngine(EngineBase):
    """Lockstep micro-batching engine (protocol adapter over padded batches).

    ``step()`` semantics: with no batch in flight, pull up to ``max_batch``
    requests from the admission queue, left-pad to the longest prompt,
    prefill and sample each row's first token. Every further ``step()`` runs
    one decode step over the whole batch. Rows finish independently (length
    / stop / cancel) and are masked until the slowest row retires the batch
    — the classic lockstep cost the continuous batcher removes.
    """

    def __init__(self, cfg, params, *, max_len: int = 256, seed: int = 0,
                 max_batch: int = 8,
                 admission: AdmissionPolicy | None = None):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.max_len = max_len
        self.max_batch = max_batch
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, self.max_len)
        )
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))
        # jitted (per batch width): eager vmap would re-trace the sampler's
        # per-row body on every decode step; greedy_only is static so
        # all-greedy batches pay a plain argmax (same trick as the paged
        # engine's fused decode step)
        def _sample_fn(lg, temps, tks, tps, seeds, idx, greedy_only):
            if greedy_only:
                return jnp.argmax(
                    lg[..., :cfg.vocab_size], axis=-1
                ).astype(jnp.int32)
            return sample_tokens(lg, temps, tks, tps, seeds, idx,
                                 cfg.vocab_size)

        self._sample = jax.jit(_sample_fn, static_argnums=(6,))
        self._init_api(admission=admission, seed=seed)
        self.utilization = UtilizationMetrics()
        self._batch: list[_Row] | None = None
        self._bstate: dict | None = None

    # -- EngineBase hooks ----------------------------------------------
    def _validate(self, request: Request) -> None:
        validate_request(request, max_len=self.max_len)

    def _cancel_active(self, uid: str) -> bool:
        if self._batch is None:
            return False
        for row in self._batch:
            if row.handle.uid == uid and not row.done:
                row.done = True
                self._finish_handle(row.handle, FinishReason.CANCELLED)
                self._retire_if_done()
                return True
        return False

    def _retire_if_done(self) -> None:
        if self._batch is not None and all(r.done for r in self._batch):
            self._batch = None
            self._bstate = None

    # -- protocol -------------------------------------------------------
    @property
    def idle(self) -> bool:
        return not (len(self.admission) or self._batch or self._events)

    def capacity(self) -> int:
        if self._batch is not None:
            return 0
        return max(0, self.max_batch - len(self.admission))

    def step(self) -> list[StreamEvent]:
        now = time.perf_counter()
        self._expire_queue(now)
        if self._batch is None:
            # batch bound: rows are left-padded to the longest prompt and
            # decode until the slowest row finishes, so the batch occupies
            # max(plen) + max(max_new) cache positions — admit only while
            # that fits max_len (a lone request always does: validated)
            reqs: list[Request] = []
            plen = new = 0
            while len(reqs) < self.max_batch:
                cand = self.admission.peek(now)
                if cand is None:
                    break
                c_plen = max(plen, len(cand.prompt))
                c_new = max(new, cand.sampling.max_new_tokens)
                if reqs and c_plen + c_new > self.max_len:
                    break
                plen, new = c_plen, c_new
                reqs.append(self.admission.pop(now))
            if reqs:
                self._start_batch(reqs)
        else:
            st = self._bstate
            self.utilization.record(
                active=sum(not r.done for r in self._batch),
                slots=self.max_batch,
            )
            st["cache"], logits = self._decode(
                self.params, st["cache"], st["tok"][:, None]
            )
            st["step"] += 1
            st["tok"] = self._sample(
                logits, st["temps"], st["tks"], st["tps"], st["seeds"],
                jnp.full((len(self._batch),), st["step"], jnp.int32),
                st["greedy_only"],
            )
            self._harvest(np.asarray(st["tok"]))
        self._retire_if_done()
        return self._drain_events()

    # -- internals ------------------------------------------------------
    def _start_batch(self, reqs: list[Request]) -> None:
        b = len(reqs)
        plen = max(len(r.prompt) for r in reqs)
        toks = np.zeros((b, plen), np.int32)
        for i, r in enumerate(reqs):
            toks[i, plen - len(r.prompt):] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "vlm":
            batch["vision_embeds"] = jnp.zeros(
                (b, self.cfg.num_frontend_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype),
            )
        if self.cfg.is_encoder_decoder:
            batch["frames"] = jnp.zeros(
                (b, plen, self.cfg.d_model), jnp.dtype(self.cfg.dtype)
            )
        rows = [_Row(r, self._handles[r.uid]) for r in reqs]
        for row in rows:
            self._stamp(row.handle, "admitted")
            self._stamp(row.handle, "prefill_start")
        cache, logits = self._prefill(self.params, batch)
        sp = [r.sampling for r in reqs]
        st = {
            "cache": cache,
            "step": 0,
            "greedy_only": all(s.temperature <= 0.0 for s in sp),
            "temps": jnp.asarray([s.temperature for s in sp], jnp.float32),
            "tks": jnp.asarray([s.top_k for s in sp], jnp.int32),
            "tps": jnp.asarray([s.top_p for s in sp], jnp.float32),
            "seeds": jnp.asarray([row.handle.seed for row in rows], jnp.int32),
        }
        st["tok"] = self._sample(
            logits, st["temps"], st["tks"], st["tps"], st["seeds"],
            jnp.zeros((b,), jnp.int32), st["greedy_only"],
        )
        self._batch, self._bstate = rows, st
        self._harvest(np.asarray(st["tok"]))

    def _harvest(self, toks: np.ndarray) -> None:
        now = time.perf_counter()
        idx = self._bstate["step"]
        for i, row in enumerate(self._batch):
            if row.done:
                continue
            if self._deliver(row.handle, int(toks[i]), idx, now):
                row.done = True


# ---------------------------------------------------------------------------
# continuous batching (scheduler/executor split)
# ---------------------------------------------------------------------------


class ContinuousBatchingEngine(EngineBase):
    """Paged-KV continuous batcher for decoder-only attention families.

    Protocol adapter over the scheduler/executor split:

    * the :class:`Scheduler` decides (host-only) — admission against the
      prefix index and the page pool, one prefill chunk per step
      interleaved with decode, youngest-first preemption under pool
      pressure, decode-batch assembly;
    * the :class:`ModelExecutor` computes (device-only) — one jitted
      sharded dispatch per chunk / per decode step over ``max_slots``
      fixed-width slots; idle or prefilling slots are masked (null block
      table, length 0) and their attention output discarded;
    * this class translates between them and the
      :class:`~repro.serving.api.EngineCore` lifecycle: handles, stream
      events, typed finishes, preemption-transparent requeueing.

    Sequences finish independently — their page refcounts drop (pages
    return to the pool at zero) and the slot is refilled from the waiting
    queue on the next step.

    With prefix sharing on, a :class:`~repro.serving.kv_tiers.KVTierManager`
    (``kv_tiers``; default follows ``prefix_sharing``) parks released
    prefix pages instead of freeing them, reclaiming them lazily under pool
    pressure; ``host_pages``/``persist_dir`` add host-RAM and
    ArtifactStore-backed spill tiers with async prefetch on prefix hits.
    ``kv_quant="int8"`` stores KV pages quantized per page per head, with
    dequantization fused into the paged attention kernels — roughly halving
    page bytes at equal pool capacity.
    """

    def __init__(
        self,
        cfg,
        params,
        *,
        max_len: int = 256,
        max_slots: int = 8,
        page_size: int = 16,
        num_pages: int | None = None,
        seed: int = 0,
        attn_impl: str | None = None,
        prefill_chunk: int | None = 64,
        prefix_sharing: bool = True,
        admission: AdmissionPolicy | None = None,
        max_preemptions: int | None = None,
        step_mode: str = "fused",
        token_budget: int | None = None,
        kv_quant: str = "none",
        kv_tiers: bool | None = None,
        host_pages: int = 0,
        persist_dir: str | None = None,
        speculative: str = "off",
        spec_k: int = 4,
        draft_config=None,
        draft_params=None,
    ):
        assert not cfg.is_encoder_decoder, "paged engine is decoder-only"
        assert cfg.family in ("dense", "moe", "vlm"), (
            f"continuous batching needs a paged KV path; family "
            f"{cfg.family!r} should use GenerationEngine"
        )
        self.cfg = cfg
        self.nf = cfg.num_frontend_tokens if cfg.family == "vlm" else 0
        self.max_len = max_len
        self.max_slots = max_slots
        self.max_preemptions = max_preemptions
        if prefill_chunk == 0:  # CLI convention: 0 disables chunking
            prefill_chunk = None
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        # vlm prompts carry vision embeds: no token chunking, no prefix trie
        self._chunked = prefill_chunk is not None and cfg.family in ("dense", "moe")
        self.prefill_chunk = prefill_chunk
        self.prefix_sharing = prefix_sharing and self._chunked
        if step_mode not in ("fused", "interleaved"):
            raise ValueError(
                f"step_mode must be 'fused' or 'interleaved', got {step_mode!r}"
            )
        if token_budget is not None and token_budget < 1:
            raise ValueError(f"token_budget must be >= 1, got {token_budget}")
        self.step_mode = step_mode
        self.token_budget = token_budget
        if kv_quant not in ("none", "int8"):
            raise ValueError(
                f"kv_quant must be 'none' or 'int8', got {kv_quant!r}"
            )
        # tiers default to on whenever the prefix index exists to park into
        # (kv_tiers=False forces them off for A/B runs; host/persist tiers
        # only engage when host_pages / persist_dir are set)
        if kv_tiers is None:
            kv_tiers = self.prefix_sharing
        self.tiers = (
            KVTierManager(
                host_pages=host_pages,
                store=(ArtifactStore(persist_dir)
                       if persist_dir is not None else None),
            )
            if kv_tiers and self.prefix_sharing else None
        )
        self.cache = PagedKVCache(
            num_layers=cfg.num_layers,
            num_kv_heads=cfg.eff_kv_heads,
            head_dim=cfg.head_dim,
            dtype=jnp.dtype(cfg.dtype),
            max_slots=max_slots,
            max_context=max_len,
            page_size=page_size,
            num_pages=num_pages,
            quant=kv_quant,
            tiers=self.tiers,
        )
        self.scheduler = Scheduler(
            self.cache,
            prefill_chunk=prefill_chunk,
            chunked=self._chunked,
            prefix_sharing=self.prefix_sharing,
            extra_ctx=self.nf,
            token_budget=token_budget,
        )
        self.executor = ModelExecutor(
            cfg, params, self.cache, max_len=max_len, attn_impl=attn_impl
        )
        self.model = self.executor.model
        self.params = self.executor.params
        # speculative decoding: a proposer drafts spec_k tokens per
        # decoding slot; the executor verifies each bundle in one fused
        # dispatch; rejected tails roll back by rewinding sequence length
        if speculative not in SPEC_MODES:
            raise ValueError(
                f"speculative must be one of {SPEC_MODES}, got {speculative!r}"
            )
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.spec_mode = speculative
        self.spec_k = spec_k
        self.spec = None
        if speculative != "off":
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"speculative decoding needs the paged chunk path; "
                    f"family {cfg.family!r} has none"
                )
            if step_mode != "fused":
                raise ValueError(
                    "speculative decoding requires step_mode='fused'"
                )
            self.spec = build_proposer(
                speculative, draft_config=draft_config,
                draft_params=draft_params, max_slots=max_slots,
                max_len=max_len, page_size=page_size, seed=seed,
                attn_impl=attn_impl,
            )
        self._init_api(admission=admission, seed=seed)
        self.utilization = UtilizationMetrics()
        self.stats.update({"decode_steps": 0, "prefills": 0,
                           "prefill_chunks": 0, "preemptions": 0,
                           "spec_bundles": 0})

    # ------------------------------------------------------------------
    # EngineBase hooks
    # ------------------------------------------------------------------
    def _validate(self, request: Request) -> None:
        validate_request(request, max_len=self.max_len, extra_ctx=self.nf)
        ctx = self.nf + len(request.prompt)
        worst = cdiv(ctx + request.sampling.max_new_tokens,
                     self.cache.page_size)
        if worst > self.cache.num_pages - 1:
            raise ValueError(
                f"request {request.uid}: needs {worst} KV pages, pool has "
                f"{self.cache.num_pages - 1} — it could never be scheduled"
            )

    def _release_slot(self, slot: int) -> Sequence:
        """Release a slot and retire any proposer state for its uid —
        every engine-side release funnels through here so a finished or
        cancelled request can never leak a draft-cache slot."""
        seq = self.scheduler.release(slot)
        if self.spec is not None:
            self.spec.retire(seq.request.uid)
        return seq

    def _cancel_active(self, uid: str) -> bool:
        slot = self.scheduler.find(uid)
        if slot is None:
            return False
        seq = self._release_slot(slot)
        self._finish_handle(seq.handle, FinishReason.CANCELLED)
        return True

    # ------------------------------------------------------------------
    # protocol surface
    # ------------------------------------------------------------------
    @property
    def idle(self) -> bool:
        return not (len(self.admission) or self.scheduler.slots
                    or self._events)

    def capacity(self) -> int:
        return max(0, self.cache.free_slot_count - len(self.admission))

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def _first_token(self, slot: int, seq: Sequence, tok: int) -> None:
        """Prompt fully cached: deliver the sampled first token (attempt
        index 0 — after a preemption the handle de-duplicates it)."""
        now = time.perf_counter()
        seq.tokens.append(tok)
        self.scheduler.begin_decode(slot)
        self.stats["prefills"] += 1
        if self._deliver(seq.handle, tok, 0, now):
            # finish event lands in THIS step's batch (admit/prefill run
            # before the decode harvest) — not delayed to the next one
            self._release_slot(slot)

    def _admit(self) -> int:
        with phase("admit"):
            now = time.perf_counter()
            self._expire_queue(now)
            admitted = 0
            while True:
                req = self.admission.peek(now)
                if req is None or not self.scheduler.can_place(req):
                    break
                self.admission.pop(now)
                handle = self._handles[req.uid]
                slot, seq, _ = self.scheduler.place(req, handle)
                self._stamp(handle, "admitted")
                admitted += 1
                if not self._chunked:
                    # legacy whole-prompt path (vlm / prefill_chunk=None):
                    # one executor dispatch per admission
                    self._stamp(handle, "prefill_start")
                    tok = self.executor.prefill_whole(req, handle.seed, slot)
                    self._first_token(slot, seq, tok)
            return admitted

    def _prefill_step(self) -> bool:
        """Advance the oldest in-flight prefill by one fixed-size chunk
        (scheduler picks, executor dispatches)."""
        with phase("plan"):
            work = self.scheduler.next_prefill()
        if work is None:
            return False
        self._stamp(work.seq.handle, "prefill_start")
        tok = self.executor.prefill_chunk(work)
        with phase("deliver"):
            self.stats["prefill_chunks"] += 1
            if self.scheduler.complete_chunk(work):
                self._first_token(work.slot, work.seq, tok)
        return True

    def _handle_preempted(self, seq: Sequence) -> None:
        """Bookkeeping for a sequence the scheduler evicted under pool
        pressure: requeue transparently (already-streamed deltas are never
        re-emitted) or finish ``preempted`` past ``max_preemptions``."""
        self.stats["preemptions"] += 1
        if self.spec is not None:
            self.spec.retire(seq.request.uid)
        h = seq.handle
        h.preemptions += 1
        if (self.max_preemptions is not None
                and h.preemptions > self.max_preemptions):
            self._finish_handle(
                h, FinishReason.PREEMPTED,
                error=f"request {h.uid}: preempted {h.preemptions} times "
                      f"(max_preemptions={self.max_preemptions})",
            )
        else:
            self._events.append(
                StreamEvent(h.uid, "preempted", t=time.perf_counter())
            )
            self.admission.requeue(seq.request, h.arrival)

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------
    def step(self) -> list[StreamEvent]:
        """Run one engine step and return the lifecycle events produced
        (token deltas, finishes, preemptions).

        ``step_mode="fused"`` (default): admit, build ONE token-budgeted
        :class:`~repro.serving.scheduler.StepPlan` and dispatch it — every
        decode slot and (at most) one prefill chunk in a single
        Pallas-backed executor call. ``step_mode="interleaved"`` keeps the
        pre-fusion behavior (one chunk dispatch, then one decode dispatch)
        for A/B comparison; both modes produce byte-identical streams.
        The step is a ``serving.step`` span split into phases
        (``serving/metrics.py``)."""
        with self.utilization.step():
            if self.step_mode == "interleaved":
                return self._step_interleaved()
            return self._step_fused()

    def _record_batch(self, decode_rows: int, prefill_live: int,
                      rows: int, fused: bool) -> None:
        self.utilization.record_batch(
            decode_rows=decode_rows, prefill_rows=prefill_live,
            padded_rows=rows - decode_rows - prefill_live, fused=fused,
        )

    def _dispatch_plan(self, plan) -> np.ndarray | None:
        """Run one plan through the executor and do the chunk bookkeeping
        (cursor advance, prefix publication, first-token delivery). Returns
        the decode tokens for the engine harvest (None: no decode rows)."""
        chunk, n_dec = plan.chunk, len(plan.decode_slots)
        rows = n_dec and self.max_slots
        if chunk is not None:
            rows += len(chunk.tokens)
        self._record_batch(n_dec, chunk.valid if chunk else 0, rows,
                           fused=bool(chunk is not None and n_dec))
        if chunk is not None:
            self._stamp(chunk.seq.handle, "prefill_start")
        toks, ctok = self.executor.step(plan)
        if chunk is not None:
            with phase("deliver"):
                self.stats["prefill_chunks"] += 1
                if self.scheduler.complete_chunk(chunk):
                    self._first_token(chunk.slot, chunk.seq, ctok)
        return toks

    def _record_tiers(self) -> None:
        if self.tiers is not None:
            t = self.tiers
            self.utilization.record_tiers(
                parked=t.parked_count, host=t.host_count,
                persisted=t.persisted_count, counters=t.counters,
            )

    # ------------------------------------------------------------------
    # speculative decoding
    # ------------------------------------------------------------------
    def _propose_bundles(self) -> dict[int, list[int]]:
        """Ask the proposer for drafts for every decoding slot. ``k`` is
        capped so a fully-accepted bundle can neither overshoot the
        request's validated worst-case page budget (context + k + 1 must
        stay within max_pages_per_seq) nor draft past max_new_tokens
        (tokens beyond the finish are pure waste)."""
        out: dict[int, list[int]] = {}
        cache = self.cache
        ctx_cap = cache.max_pages_per_seq * cache.page_size
        for slot, seq in self.scheduler.decoding():
            sp = seq.request.sampling
            if not sp.speculative:
                continue
            k = min(self.spec_k,
                    sp.max_new_tokens - len(seq.tokens) - 1,
                    ctx_cap - int(cache.lengths[slot]) - 1)
            if k < 1:
                continue
            history = list(seq.request.prompt) + seq.tokens
            drafts = self.spec.propose(seq.request.uid, history, k)
            if drafts:
                out[slot] = drafts[:k]
        return out

    def _harvest_bundle(self, bundle, now: float) -> None:
        """Dispatch one verify bundle and commit its outcome.

        The verify step sampled a token for every bundle row under the
        same ``(seed, token_index)`` key sequential decode would have
        used, so acceptance is a pure host-side comparison: ``a`` = length
        of the leading run where the sampled token equals the draft. Rows
        0..a hold KV for tokens the sampler itself produced — commit
        advances the cached length to ``start + a + 1`` and the rejected
        tail is rewound by never advancing past it (append-only pages:
        nothing to free, nothing published — ``register_prefix`` only runs
        during prefill). ``sampled[a]`` is the bonus/correction token; its
        KV is not cached yet, exactly like a plain decode step's newest
        token. Emission goes through the same ``_deliver`` path as plain
        decode, so stop/length finishes mid-bundle release the slot and
        drop the unemitted remainder."""
        sched = self.scheduler
        toks = self.executor.verify(bundle)
        k = len(bundle.drafts)
        a = 0
        while a < k and int(toks[a]) == bundle.drafts[a]:
            a += 1
        sched.commit_speculation(bundle.slot, bundle.start + a + 1)
        self.stats["spec_bundles"] += 1
        self.utilization.record_spec(proposed=k, accepted=a,
                                     rollbacks=k - a)
        seq = bundle.seq
        for j in range(a + 1):
            tok = int(toks[j])
            sched.append_speculated(bundle.slot, tok)
            if self._deliver(seq.handle, tok, len(seq.tokens) - 1, now):
                self._release_slot(bundle.slot)
                break

    def _step_fused(self) -> list[StreamEvent]:
        sched = self.scheduler
        # publish last step's prefetched pages BEFORE admission matches
        # against the prefix index (pending pages stay invisible one step)
        self.cache.tick_tiers()
        self._admit()
        # with no decode in flight there is no stall to bound, so drain
        # chunk-only plans back-to-back until a sequence becomes decodable
        # (cold start, post-burst refill)
        while not sched.has_decodable():
            with phase("plan"):
                plan = sched.build_step_plan()
            if plan.chunk is None:
                return self._drain_events()
            self._dispatch_plan(plan)
            self._admit()

        with phase("plan"):
            # every decode row needs a writable page BEFORE the plan
            # captures block tables (growth/COW dirties them; eviction can
            # also claim the slot a chunk would have targeted). Speculating
            # slots pre-grow k extra positions so the verify dispatch never
            # hits a page fault.
            proposals = (self._propose_bundles()
                         if self.spec is not None else {})
            extra = ({s: len(d) for s, d in proposals.items()}
                     if proposals else None)
            for seq in sched.ensure_decode_capacity(extra=extra):
                self._handle_preempted(seq)
            if not sched.has_decodable():
                # preemption can empty the decode set
                return self._drain_events()

            decoding, slots = sched.occupancy()
            used, total = sched.page_utilization()
            self.utilization.record(active=decoding, slots=slots,
                                    pages_used=used, pages_total=total)
            self._record_tiers()
            # eviction may have dropped a proposal's sequence — bundle only
            # slots that still hold the decoding sequence we drafted for
            bundles = [
                sched.build_spec_bundle(s, d, self.spec_k + 1)
                for s, d in sorted(proposals.items())
                if sched.slots.get(s) is not None
                and sched.slots[s].phase == "decode"
            ]
            plan = sched.build_step_plan(spec=bundles)
        toks = None
        if plan.decode_slots or plan.chunk is not None:
            toks = self._dispatch_plan(plan)
        with phase("deliver"):
            self.stats["decode_steps"] += 1
            now = time.perf_counter()
            # harvest exactly the slots the plan dispatched — the chunk slot
            # may have become decodable mid-step and is NOT in this batch
            for slot in plan.decode_slots:
                seq = sched.slots[slot]
                tok = int(toks[slot])
                sched.append_decoded(slot, tok)
                if self._deliver(seq.handle, tok, len(seq.tokens) - 1, now):
                    self._release_slot(slot)
            # bundled slots step through their verify dispatch instead
            for bundle in plan.spec or ():
                self._harvest_bundle(bundle, now)
            self._record_tiers()  # post-release: captures end-of-life parking
            return self._drain_events()

    def _step_interleaved(self) -> list[StreamEvent]:
        """Pre-fusion step: one chunk dispatch interleaved with one decode
        dispatch (kept for A/B against the fused step)."""
        sched = self.scheduler
        self.cache.tick_tiers()
        self._admit()
        ran = self._prefill_step()
        # the one-chunk-per-step cap exists to bound decode stalls; with no
        # decode in flight there is nothing to stall, so drain chunks
        # back-to-back until a sequence becomes decodable (cold start,
        # post-burst refill)
        while ran and not sched.has_decodable():
            self._admit()
            ran = self._prefill_step()
        if not sched.has_decodable():
            return self._drain_events()

        with phase("plan"):
            for seq in sched.ensure_decode_capacity():
                self._handle_preempted(seq)
            if not sched.has_decodable():
                # preemption can empty the decode set
                return self._drain_events()

            decoding, slots = sched.occupancy()
            used, total = sched.page_utilization()
            self.utilization.record(active=decoding, slots=slots,
                                    pages_used=used, pages_total=total)
            self._record_tiers()
            self._record_batch(decoding, 0, self.max_slots, fused=False)
            inputs = sched.build_decode_inputs() if sched.dirty else None
        toks = self.executor.decode(inputs)
        with phase("deliver"):
            self.stats["decode_steps"] += 1
            now = time.perf_counter()
            for slot, seq in sched.decoding():
                tok = int(toks[slot])
                sched.append_decoded(slot, tok)
                if self._deliver(seq.handle, tok, len(seq.tokens) - 1, now):
                    self._release_slot(slot)
            self._record_tiers()  # post-release: captures end-of-life parking
            return self._drain_events()
