"""SSM/hybrid continuous-batching engine: per-slot recurrent state.

Mamba2 serving is the page-pool design turned inside out: a sequence's
whole history is a CONSTANT-SIZE recurrent state (the ``init_mamba_cache``
pytree — f32 SSD state ``(H, P, N)`` plus three conv tails), so instead of
a :class:`~repro.serving.kv_cache.PagedKVCache` the engine owns a
:class:`SlotStateBank` — that pytree stacked over layers and batched over
slots. Admission binds a request to a bank slot; chunked prefill runs the
prompt through ``ops.ssd_scan`` (carrying the state chunk-to-chunk, padded
tail positions neutralized by dt=0); decode is ONE fused jitted
dispatch per step under ``shard_map`` on the ``("model",)`` mesh — state
sharded on ``ssm_heads`` / ``ff`` per ``MAMBA_CACHE_AXES``, sampled tokens
returning replicated, with the same packed device-mirror feedback loop as
:class:`~repro.serving.executor.ModelExecutor` (zero host->device
transfers in steady state).

Fault tolerance is where constant-size state pays: :meth:`SSMEngine
.preempt_youngest` evicts the youngest decoding sequence either by
discarding its state (default — the requeued request re-prefills and the
``(seed, token_index)``-keyed sampler regenerates a byte-identical stream,
already-emitted deltas de-duplicated by the handle) or with
``snapshot=True`` by parking the slot's state pytree on the host, restored
verbatim at re-admission so the sequence resumes decoding WITHOUT
re-prefill. The fleet crash-replay path (PR 7) needs no engine-specific
work: replayed requests re-prefill deterministically exactly like a
discarded preemption.

The hybrid (Zamba2) case routes the shared attention block through a
``PagedKVCache`` sized for ``num_layers // attn_every`` layers and every
Mamba layer through the state bank in the SAME fused step; attention page
exhaustion preempts youngest-first exactly like the paged engine.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import build_model
from repro.models.common import sample_tokens
from repro.models.ssm import init_mamba_cache
from repro.parallel.collectives import tensor_parallel
from repro.serving.api import (
    EngineBase,
    FinishReason,
    Request,
    StreamEvent,
    validate_request,
)
from repro.serving.executor import (
    PAGE_SPEC,
    _serving_param_specs,
    default_serving_mesh,
    validate_serving_mesh,
)
from repro.serving.kv_cache import NULL_PAGE, PagedKVCache, cdiv
from repro.serving.metrics import UtilizationMetrics, phase
from repro.serving.scheduler import DecodeInputs, Sequence

__all__ = ["SSMEngine", "SlotStateBank"]

# Stacked-bank PartitionSpecs, MAMBA_CACHE_AXES with a leading layer axis
# and the cache_batch axis reinterpreted as the slot axis: the SSD state
# shards on ssm_heads, the x conv tail on its d_inner channels, and the
# B/C conv tails (state-dim N, replicated projections) stay replicated.
STATE_SPECS = {
    "ssm": P(None, None, "model", None, None),     # (L, S, HN, PN, N)
    "conv_x": P(None, None, None, "model"),        # (L, S, W-1, DIN)
    "conv_b": P(),                                 # (L, S, W-1, N)
    "conv_c": P(),
}


class SlotStateBank:
    """The per-slot recurrent-state bank: ``init_mamba_cache`` stacked over
    layers (leading axis L) and batched over slots (second axis S).

    The bank is a plain pytree of device arrays — the executor's fused
    step functions take it as a donated argument and hand back the
    advanced bank, so steady-state decode never copies it. Host-side slot
    bookkeeping (which slot belongs to which request) lives in the engine;
    the bank only knows shapes, snapshots and restores.
    """

    def __init__(self, cfg, max_slots: int, dtype) -> None:
        mc = init_mamba_cache(cfg, max_slots, dtype, abstract=True)
        self.state: dict[str, jax.Array] = {
            k: jnp.zeros((cfg.num_layers,) + s.shape, s.dtype)
            for k, s in mc.items()
        }
        self.max_slots = max_slots
        self.shardings: dict | None = None  # set by the executor when tp > 1

    def commit(self, state: dict) -> None:
        """Adopt an updated bank, re-pinning the serving sharding after
        host-side slot surgery (restore) so the jitted steps see their
        expected layout."""
        if self.shardings is not None:
            state = {
                k: jax.device_put(v, self.shardings[k])
                for k, v in state.items()
            }
        self.state = state

    def snapshot(self, slot: int) -> dict[str, np.ndarray]:
        """Copy one slot's full state pytree to the host — (L, ...) leaves
        with the slot axis dropped."""
        return {k: np.asarray(v[:, slot]) for k, v in self.state.items()}

    def restore(self, slot: int, snap: dict[str, np.ndarray]) -> None:
        """Write a host snapshot back into a (newly allocated) slot."""
        self.commit({
            k: v.at[:, slot].set(jnp.asarray(snap[k], v.dtype))
            for k, v in self.state.items()
        })


class SSMExecutor:
    """Compute half of the SSM engine: jitted fused decode+sample and
    chunked-prefill step functions under ``shard_map``, plus the packed
    device mirrors of the decode batch (same ``di``/``df`` packing,
    steady-state zero-transfer loop and ``pack``/``dispatch``/``sync``
    phases as :class:`~repro.serving.executor.ModelExecutor`)."""

    # di (S, MP+6) int32: block-table row (MP=0 for pure SSM), then
    # [lens, active, tokens, top_ks, seeds, idx]; df (S, 2) f32:
    # [temps, top_ps]. lens only drives attention in the hybrid case but
    # is advanced uniformly so both layouts share one packing.
    _DI_COLS = 6

    def __init__(self, cfg, params, bank: SlotStateBank,
                 cache: PagedKVCache | None, *, max_len: int,
                 mesh: Mesh | None = None, attn_impl: str | None = None,
                 ssd_impl: str | None = None):
        self.cfg = cfg
        # "auto": Pallas SSD/attention kernels on TPU, the XLA reference
        # lowering elsewhere — same contract either way (kernel fuzz suite)
        self.model = build_model(
            cfg, attn_impl=attn_impl or "auto", ssd_impl=ssd_impl or "auto"
        )
        self.bank = bank
        self.cache = cache
        self.max_len = max_len
        self.mesh = mesh if mesh is not None else default_serving_mesh(cfg)
        self.tp = validate_serving_mesh(cfg, self.mesh)
        self.vocab_sharded = (not cfg.tie_embeddings) and self.tp > 1
        self.param_specs = _serving_param_specs(
            self.model, self.mesh, self.vocab_sharded
        )
        self.params = self._place(params)
        self._decode_fns: dict[bool, object] = {}
        self._chunk_fns: dict[bool, object] = {}
        self._greedy_only = True
        self._di = self._df = None

    # ------------------------------------------------------------------
    # sharding
    # ------------------------------------------------------------------
    def _place(self, params):
        if self.tp == 1:
            return params
        ns = lambda spec: NamedSharding(self.mesh, spec)
        placed = jax.tree.map(
            lambda arr, spec: jax.device_put(arr, ns(spec)),
            params, self.param_specs,
        )
        self.bank.shardings = {k: ns(s) for k, s in STATE_SPECS.items()}
        self.bank.commit(self.bank.state)
        if self.cache is not None:
            self.cache._reshard(
                {key: ns(spec) for key, spec in self._page_specs().items()}
            )
        return placed

    def _page_specs(self) -> dict:
        return {key: PAGE_SPEC for key in self.cache.pages}

    def _smap(self, fn, in_specs, out_specs):
        # replication checking off: scatters and gathered logits are
        # replicated by construction, which the checker cannot prove
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def _tp_ctx(self):
        return tensor_parallel("model", vocab_sharded=self.vocab_sharded)

    def _sample(self, logits, di, df, mp, greedy_only):
        if greedy_only:
            return jnp.argmax(
                logits[..., :self.cfg.vocab_size], axis=-1
            ).astype(jnp.int32)
        return sample_tokens(logits, df[:, 0], di[:, mp + 3], df[:, 1],
                             di[:, mp + 4], di[:, mp + 5],
                             self.cfg.vocab_size)

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _decode_fn(self, greedy_only: bool):
        """ONE dispatch per decode step: every slot's recurrent-state step
        (plus the shared attention pool read/write in the hybrid case) and
        sampling fused; logits never leave the device. The donated state
        bank comes back advanced, idle slots' state writeback gated by the
        ``active`` column."""
        if greedy_only not in self._decode_fns:
            if self.cache is None:
                def fn(params, state, di, df):
                    mp = di.shape[1] - self._DI_COLS
                    lens, active = di[:, mp], di[:, mp + 1]
                    with self._tp_ctx():
                        state, logits = self.model.decode_step_ssm(
                            params, state, di[:, mp + 2:mp + 3], active
                        )
                        toks = self._sample(logits, di, df, mp, greedy_only)
                    di = di.at[:, mp].set(lens + active)
                    di = di.at[:, mp + 2].set(toks)
                    di = di.at[:, mp + 5].add(active)
                    return state, di, toks

                smapped = self._smap(
                    fn,
                    in_specs=(self.param_specs, STATE_SPECS) + (P(),) * 2,
                    out_specs=(STATE_SPECS, P(), P()),
                )
                self._decode_fns[greedy_only] = jax.jit(
                    smapped, donate_argnums=(1, 2)
                )
            else:
                def fn(params, pages, state, di, df):
                    mp = di.shape[1] - self._DI_COLS
                    bt, lens, active = di[:, :mp], di[:, mp], di[:, mp + 1]
                    with self._tp_ctx():
                        pages, state, logits = self.model.decode_step_hybrid(
                            params, pages, state, bt, lens,
                            di[:, mp + 2:mp + 3], active,
                        )
                        toks = self._sample(logits, di, df, mp, greedy_only)
                    di = di.at[:, mp].set(lens + active)
                    di = di.at[:, mp + 2].set(toks)
                    di = di.at[:, mp + 5].add(active)
                    return pages, state, di, toks

                page_specs = self._page_specs()
                smapped = self._smap(
                    fn,
                    in_specs=(self.param_specs, page_specs, STATE_SPECS)
                    + (P(),) * 2,
                    out_specs=(page_specs, STATE_SPECS, P(), P()),
                )
                self._decode_fns[greedy_only] = jax.jit(
                    smapped, donate_argnums=(1, 2, 3)
                )
        return self._decode_fns[greedy_only]

    def refresh(self, inputs: DecodeInputs) -> None:
        """Mirror a freshly assembled decode batch to the device (two
        transfers: packed int32 + packed f32)."""
        with phase("pack"):
            self._greedy_only = inputs.greedy_only
            bt = inputs.block_tables
            s, mp = bt.shape
            di = np.empty((s, mp + self._DI_COLS), np.int32)
            di[:, :mp] = bt
            di[:, mp] = inputs.lengths
            di[:, mp + 1] = inputs.active
            di[:, mp + 2] = inputs.tokens[:, 0]
            di[:, mp + 3] = inputs.top_ks
            di[:, mp + 4] = inputs.seeds
            di[:, mp + 5] = inputs.idx
            self._di = jnp.asarray(di)
            self._df = jnp.asarray(np.stack(
                [inputs.temps, inputs.top_ps], axis=1).astype(np.float32))

    def decode(self, inputs: DecodeInputs | None = None) -> np.ndarray:
        """Run one decode step; ``None`` reuses the device-advanced batch
        from last step (steady state transfers nothing to the device).
        Returns the sampled token per slot, (S,) int32 on the host."""
        if inputs is not None:
            self.refresh(inputs)
        with phase("dispatch"):
            fn = self._decode_fn(self._greedy_only)
            if self.cache is None:
                state, self._di, toks = fn(
                    self.params, self.bank.state, self._di, self._df
                )
            else:
                pages = dict(self.cache.pages)
                pages, state, self._di, toks = fn(
                    self.params, pages, self.bank.state, self._di, self._df
                )
                self.cache.swap_pages(pages)
            self.bank.state = state
        with phase("sync"):
            return np.asarray(toks)

    # ------------------------------------------------------------------
    # chunked prefill
    # ------------------------------------------------------------------
    def _chunk_fn(self, greedy_only: bool):
        """One fixed-size prompt chunk of ONE sequence: dynamic-slice the
        slot's state out of the bank (zeroed when ``start == 0`` so a
        recycled slot never leaks its previous occupant), run the SSD scan
        continuation, scatter the advanced state back, and sample the
        chunk's token (meaningful on the final chunk)."""
        if greedy_only not in self._chunk_fns:
            cfg = self.cfg

            def slot_state(state, slot, start):
                sl = {
                    k: jax.lax.dynamic_index_in_dim(v, slot, axis=1,
                                                    keepdims=True)
                    for k, v in state.items()
                }
                fresh = start == 0
                return {
                    k: jnp.where(fresh, jnp.zeros_like(v), v)
                    for k, v in sl.items()
                }

            def put_back(state, new_sl, slot):
                return {
                    k: jax.lax.dynamic_update_index_in_dim(
                        v, new_sl[k].astype(v.dtype), slot, axis=1
                    )
                    for k, v in state.items()
                }

            def sample1(logits, ci, cf, tail):
                if greedy_only:
                    return jnp.argmax(
                        logits[:cfg.vocab_size], axis=-1
                    ).astype(jnp.int32)
                return sample_tokens(
                    logits[None], cf[0:1], ci[tail + 3:tail + 4], cf[1:2],
                    ci[tail + 4:tail + 5], jnp.zeros((1,), jnp.int32),
                    cfg.vocab_size,
                )[0]

            if self.cache is None:
                def fn(params, state, ci, cf):
                    c = ci.shape[0] - 5
                    toks, (slot, start, valid) = ci[:c], ci[c:c + 3]
                    sl = slot_state(state, slot, start)
                    with self._tp_ctx():
                        new_sl, logits = self.model.prefill_chunk_ssm(
                            params, sl, toks, valid
                        )
                        tok = sample1(logits, ci, cf, c)
                    return put_back(state, new_sl, slot), tok

                smapped = self._smap(
                    fn,
                    in_specs=(self.param_specs, STATE_SPECS) + (P(),) * 2,
                    out_specs=(STATE_SPECS, P()),
                )
                self._chunk_fns[greedy_only] = jax.jit(
                    smapped, donate_argnums=(1,)
                )
            else:
                mp = self.cache.block_tables.shape[1]

                def fn(params, pages, state, ci, cf):
                    c = ci.shape[0] - mp - 5
                    row, toks = ci[:mp], ci[mp:mp + c]
                    slot, start, valid = ci[mp + c:mp + c + 3]
                    sl = slot_state(state, slot, start)
                    with self._tp_ctx():
                        pages, new_sl, logits = (
                            self.model.prefill_chunk_hybrid(
                                params, pages, sl, row, toks, start, valid
                            )
                        )
                        tok = sample1(logits, ci, cf, mp + c)
                    return pages, put_back(state, new_sl, slot), tok

                page_specs = self._page_specs()
                smapped = self._smap(
                    fn,
                    in_specs=(self.param_specs, page_specs, STATE_SPECS)
                    + (P(),) * 2,
                    out_specs=(page_specs, STATE_SPECS, P()),
                )
                self._chunk_fns[greedy_only] = jax.jit(
                    smapped, donate_argnums=(1, 2)
                )
        return self._chunk_fns[greedy_only]

    def prefill_chunk(self, slot: int, seq: Sequence, tokens: np.ndarray,
                      start: int, valid: int) -> int:
        """Dispatch one padded chunk for ``slot``; returns the sampled
        token (the request's first token on the prompt's final chunk)."""
        sp = seq.request.sampling
        c = tokens.shape[0]
        with phase("pack"):
            if self.cache is not None:
                row = self.cache.block_tables[slot]
                m = row.shape[0]
                ci = np.empty(m + c + 5, np.int32)
                ci[:m] = row
                ci[m:m + c] = tokens
                ci[m + c:] = (slot, start, valid, sp.top_k, seq.handle.seed)
            else:
                ci = np.empty(c + 5, np.int32)
                ci[:c] = tokens
                ci[c:] = (slot, start, valid, sp.top_k, seq.handle.seed)
            cf = np.array([sp.temperature, sp.top_p], np.float32)
            ci, cf = jnp.asarray(ci), jnp.asarray(cf)
        with phase("dispatch"):
            fn = self._chunk_fn(sp.temperature <= 0)
            if self.cache is None:
                state, tok = fn(self.params, self.bank.state, ci, cf)
            else:
                pages = dict(self.cache.pages)
                pages, state, tok = fn(self.params, pages, self.bank.state,
                                       ci, cf)
                self.cache.swap_pages(pages)
            self.bank.state = state
        with phase("sync"):
            return int(tok)


class SSMEngine(EngineBase):
    """Continuous-batching :class:`~repro.serving.api.EngineCore` for the
    ``ssm`` (Mamba2) and ``hybrid`` (Zamba2) families.

    Same protocol surface and streaming semantics as
    :class:`~repro.serving.engine.ContinuousBatchingEngine` — continuous
    admission, chunked prefill interleaved with decode, transparent
    preemption, ``(seed, token_index)``-keyed sampling — over a
    :class:`SlotStateBank` instead of (pure SSM) or alongside (hybrid) a
    paged KV pool. Pure-SSM engines deliberately have NO ``cache``
    attribute: there are no pages to account for, and per-request memory
    is constant, so admission is bounded by slots alone.
    """

    def __init__(self, cfg, params, *, max_len: int = 256,
                 max_slots: int = 8, prefill_chunk: int | None = 32,
                 page_size: int = 16, num_pages: int | None = None,
                 admission=None, seed: int = 0,
                 max_preemptions: int | None = None,
                 attn_impl: str | None = None, ssd_impl: str | None = None):
        assert not cfg.is_encoder_decoder, "SSM engine is decoder-only"
        assert cfg.family in ("ssm", "hybrid"), (
            f"SSMEngine serves recurrent-state families; family "
            f"{cfg.family!r} should use the paged or lockstep engine"
        )
        self.cfg = cfg
        self.max_len = max_len
        self.max_slots = max_slots
        self.max_preemptions = max_preemptions
        if prefill_chunk == 0:  # CLI convention: 0 disables chunking
            prefill_chunk = None
        if prefill_chunk is None:
            # the state bank has no whole-prompt path; one max_len-sized
            # chunk is semantically identical (dt=0 padding is exact)
            prefill_chunk = max_len
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        self.hybrid = cfg.family == "hybrid"
        if self.hybrid:
            self.cache = PagedKVCache(
                num_layers=cfg.num_layers // cfg.attn_every,
                num_kv_heads=cfg.eff_kv_heads,
                head_dim=cfg.head_dim,
                dtype=jnp.dtype(cfg.dtype),
                max_slots=max_slots,
                max_context=max_len,
                page_size=page_size,
                num_pages=num_pages,
            )
        else:
            self._free = list(range(max_slots - 1, -1, -1))  # pop() -> slot 0 first
        self.bank = SlotStateBank(cfg, max_slots, jnp.dtype(cfg.dtype))
        self.executor = SSMExecutor(
            cfg, params, self.bank, self.cache if self.hybrid else None,
            max_len=max_len, attn_impl=attn_impl, ssd_impl=ssd_impl,
        )
        self.model = self.executor.model
        self.params = self.executor.params
        self.slots: dict[int, Sequence] = {}
        self._order = 0
        # uid -> (host state snapshot, attempt token list) parked by
        # preempt_youngest(snapshot=True)
        self._snapshots: dict[str, dict] = {}
        self._dirty = True
        self._init_api(admission=admission, seed=seed)
        self.utilization = UtilizationMetrics()
        self.stats.update({"decode_steps": 0, "prefills": 0,
                           "prefill_chunks": 0, "preemptions": 0,
                           "restores": 0})

    # ------------------------------------------------------------------
    # EngineBase hooks
    # ------------------------------------------------------------------
    def _validate(self, request: Request) -> None:
        validate_request(request, max_len=self.max_len)
        if self.hybrid:
            worst = cdiv(len(request.prompt) + request.sampling.max_new_tokens,
                         self.cache.page_size)
            if worst > self.cache.num_pages - 1:
                raise ValueError(
                    f"request {request.uid}: needs {worst} KV pages, pool "
                    f"has {self.cache.num_pages - 1} — it could never be "
                    f"scheduled"
                )

    def _find(self, uid: str) -> int | None:
        for slot, seq in self.slots.items():
            if seq.request.uid == uid:
                return slot
        return None

    def _cancel_active(self, uid: str) -> bool:
        slot = self._find(uid)
        if slot is None:
            return False
        seq = self._release(slot)
        self._finish_handle(seq.handle, FinishReason.CANCELLED)
        return True

    def _finish_handle(self, h, reason, error=None, now=None):
        self._snapshots.pop(h.uid, None)  # parked state must not leak
        super()._finish_handle(h, reason, error=error, now=now)

    # ------------------------------------------------------------------
    # protocol surface
    # ------------------------------------------------------------------
    @property
    def idle(self) -> bool:
        return not (len(self.admission) or self.slots or self._events)

    def capacity(self) -> int:
        free = (self.cache.free_slot_count if self.hybrid
                else len(self._free))
        return max(0, free - len(self.admission))

    # ------------------------------------------------------------------
    # admission + release
    # ------------------------------------------------------------------
    def _release(self, slot: int) -> Sequence:
        seq = self.slots.pop(slot)
        if self.hybrid:
            self.cache.release(slot)
        else:
            self._free.append(slot)
        self._dirty = True
        return seq

    def _admit(self) -> int:
        with phase("admit"):
            now = time.perf_counter()
            self._expire_queue(now)
            admitted = 0
            while True:
                req = self.admission.peek(now)
                if req is None:
                    break
                if self.hybrid:
                    if not self.cache.can_admit(len(req.prompt)):
                        break
                    slot, _ = self.cache.admit(len(req.prompt))
                else:
                    if not self._free:
                        break
                    slot = self._free.pop()
                self.admission.pop(now)
                handle = self._handles[req.uid]
                self._stamp(handle, "admitted")
                self._order += 1
                seq = Sequence(req, handle, [], order=self._order,
                               phase="prefill", prefill_pos=0)
                self.slots[slot] = seq
                admitted += 1
                parked = self._snapshots.pop(req.uid, None)
                if parked is not None:
                    # snapshot-preempted: resume decoding where it left
                    # off — the bank gets the parked state verbatim
                    # alongside the attempt's own token list (NOT the
                    # handle's delivered stream, which is longer when the
                    # attempt was itself a regeneration after an earlier
                    # discard preemption); its last entry is the
                    # sampled-but-not-yet-fed pending token
                    snap, attempt_tokens = parked
                    self.bank.restore(slot, snap)
                    seq.tokens = list(attempt_tokens)
                    seq.phase = "decode"
                    seq.prefill_pos = len(req.prompt)
                    self._dirty = True
                    self.stats["restores"] += 1
            return admitted

    def _first_token(self, slot: int, seq: Sequence, tok: int) -> None:
        """Prompt fully scanned into the slot state: deliver the sampled
        first token (attempt index 0 — after a preemption the handle
        de-duplicates it)."""
        now = time.perf_counter()
        seq.tokens.append(tok)
        seq.phase = "decode"
        self._dirty = True
        self.stats["prefills"] += 1
        if self._deliver(seq.handle, tok, 0, now):
            self._release(slot)

    # ------------------------------------------------------------------
    # preemption + snapshot/restore
    # ------------------------------------------------------------------
    def preempt_youngest(self, *, snapshot: bool = False) -> str | None:
        """Evict the youngest decoding sequence; returns its uid (None
        when nothing is decoding).

        Default: discard the slot's state and requeue the request — it
        re-prefills on re-admission and the ``(seed, token_index)``-keyed
        sampler regenerates a byte-identical stream (emitted deltas are
        de-duplicated). ``snapshot=True`` (pure SSM only) parks the slot's
        constant-size state pytree on the host instead; re-admission
        restores it and decoding resumes without re-prefill.
        """
        decoding = [(seq.order, slot) for slot, seq in self.slots.items()
                    if seq.phase == "decode"]
        if not decoding:
            return None
        _, slot = max(decoding)
        return self._preempt_slot(slot, snapshot=snapshot)

    def _preempt_slot(self, slot: int, snapshot: bool = False) -> str:
        seq = self.slots[slot]
        uid = seq.request.uid
        if snapshot:
            if self.hybrid:
                raise ValueError(
                    "snapshot preemption is pure-SSM only: a hybrid slot's "
                    "attention pages are released on preemption, so the "
                    "sequence must re-prefill (snapshot=False)"
                )
            if seq.phase == "decode" and seq.tokens:
                self._snapshots[uid] = (self.bank.snapshot(slot),
                                        list(seq.tokens))
        self._release(slot)
        self.stats["preemptions"] += 1
        h = seq.handle
        h.preemptions += 1
        if (self.max_preemptions is not None
                and h.preemptions > self.max_preemptions):
            self._finish_handle(
                h, FinishReason.PREEMPTED,
                error=f"request {uid}: preempted {h.preemptions} times "
                      f"(max_preemptions={self.max_preemptions})",
            )
        else:
            self._events.append(
                StreamEvent(uid, "preempted", t=time.perf_counter())
            )
            self.admission.requeue(seq.request, h.arrival)
        return uid

    def _ensure_decode_pages(self) -> None:
        """Hybrid only: grow every decoding slot's attention page chain
        before the fused step; pool exhaustion preempts youngest-first
        (the victim may be the requesting slot itself)."""
        for slot in sorted(s for s, q in self.slots.items()
                           if q.phase == "decode"):
            while slot in self.slots and self.slots[slot].phase == "decode":
                try:
                    if self.cache.ensure_append_capacity(slot):
                        self._dirty = True
                    break
                except RuntimeError:
                    decoding = [(q.order, s) for s, q in self.slots.items()
                                if q.phase == "decode"]
                    _, victim = max(decoding)
                    self._preempt_slot(victim)
                    if victim == slot:
                        break

    # ------------------------------------------------------------------
    # stepping
    # ------------------------------------------------------------------
    def _has_decodable(self) -> bool:
        return any(seq.phase == "decode" for seq in self.slots.values())

    def step(self) -> list[StreamEvent]:
        """Interleaved step: admit, advance the oldest in-flight prefill
        by one chunk, then run one fused decode dispatch over every
        decoding slot. Cold start (nothing decodable yet) drains prefill
        chunks back-to-back so the first token is never gated on an empty
        decode batch. The step is a ``serving.step`` span split into
        phases (``serving/metrics.py``)."""
        with self.utilization.step():
            self._admit()
            while not self._has_decodable():
                if not self._prefill_step():
                    return self._drain_events()
                self._admit()
            self._prefill_step()
            self._decode_once()
            return self._drain_events()

    def _prefill_step(self) -> bool:
        with phase("plan"):
            cand = [(q.order, s) for s, q in self.slots.items()
                    if q.phase == "prefill"]
            if not cand:
                return False
            _, slot = min(cand)
            seq = self.slots[slot]
            prompt = seq.request.prompt
            c = self.prefill_chunk
            start = seq.prefill_pos
            valid = min(c, len(prompt) - start)
            tokens = np.zeros(c, np.int32)
            tokens[:valid] = prompt[start:start + valid]
        self._stamp(seq.handle, "prefill_start")
        tok = self.executor.prefill_chunk(slot, seq, tokens, start, valid)
        with phase("deliver"):
            self.stats["prefill_chunks"] += 1
            self.utilization.record_batch(decode_rows=0, prefill_rows=valid,
                                          padded_rows=c - valid, fused=False)
            seq.prefill_pos += valid
            if seq.prefill_pos >= len(prompt):
                self._first_token(slot, seq, tok)
        return True

    def _decode_inputs(self) -> DecodeInputs:
        s = self.max_slots
        mp = self.cache.block_tables.shape[1] if self.hybrid else 0
        bt = np.full((s, mp), NULL_PAGE, np.int32)
        lengths = np.zeros(s, np.int32)
        active = np.zeros(s, np.int32)
        tokens = np.zeros((s, 1), np.int32)
        top_ks = np.zeros(s, np.int32)
        seeds = np.zeros(s, np.int32)
        idx = np.zeros(s, np.int32)
        temps = np.zeros(s, np.float32)
        top_ps = np.ones(s, np.float32)
        greedy = True
        for slot, seq in self.slots.items():
            if seq.phase != "decode":
                continue
            sp = seq.request.sampling
            if self.hybrid:
                bt[slot] = self.cache.block_tables[slot]
                lengths[slot] = self.cache.lengths[slot]
            active[slot] = 1
            tokens[slot, 0] = seq.tokens[-1]
            top_ks[slot] = sp.top_k
            seeds[slot] = seq.handle.seed
            idx[slot] = len(seq.tokens)
            temps[slot] = sp.temperature
            top_ps[slot] = sp.top_p
            if sp.temperature > 0:
                greedy = False
        return DecodeInputs(
            tokens=tokens, temps=temps, top_ks=top_ks, top_ps=top_ps,
            seeds=seeds, idx=idx, active=active, block_tables=bt,
            lengths=lengths, greedy_only=greedy,
        )

    def _decode_once(self) -> None:
        with phase("plan"):
            if self.hybrid:
                self._ensure_decode_pages()
            decoding = sorted(s for s, q in self.slots.items()
                              if q.phase == "decode")
            if not decoding:
                return
            inputs = self._decode_inputs() if self._dirty else None
            self._dirty = False
        if inputs is not None:
            self.executor.refresh(inputs)
        toks = self.executor.decode()
        with phase("deliver"):
            self.stats["decode_steps"] += 1
            self.utilization.record(
                active=len(decoding), slots=self.max_slots,
                pages_used=(self.cache.num_pages - 1
                            - self.cache.pool.available
                            if self.hybrid else None),
                pages_total=(self.cache.num_pages - 1
                             if self.hybrid else None),
            )
            self.utilization.record_batch(
                decode_rows=len(decoding), prefill_rows=0,
                padded_rows=self.max_slots - len(decoding), fused=False,
            )
            now = time.perf_counter()
            for slot in decoding:
                seq = self.slots[slot]
                tok = int(toks[slot])
                seq.tokens.append(tok)
                if self.hybrid:
                    self.cache.append(slot)
                if self._deliver(seq.handle, tok, len(seq.tokens) - 1, now):
                    self._release(slot)
