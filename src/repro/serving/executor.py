"""Device-side model executor for the paged serving engine.

This is the COMPUTE half of the scheduler/executor split
(``docs/serving.md``): it owns the jitted fused prefill/decode+sample step
functions and runs every one of them under ``shard_map`` on a 1-D
``("model",)`` mesh (:func:`repro.launch.mesh.make_serving_mesh`), with
Megatron-style tensor parallelism:

* attention q/kv heads, MLP ff and (untied) unembed columns are sharded
  over ``"model"``; row-parallel output projections reduce with
  ``psum_tp`` and the vocab-sharded logits gather with
  ``all_gather_logits`` (both marked inside the model code,
  identity when unsharded);
* the KV page pool is sharded along its **head** dimension
  (``(L, P, page, KVH, Dh)`` -> ``P(None, None, None, "model", None)``;
  int8 pools add per-page scale arrays sharded the same way minus the
  ``Dh`` axis), so every shard holds the SAME pages for its slice of heads — block
  tables, page ids, refcounts and the prefix index stay single host-side
  structures in the :class:`~repro.serving.scheduler.Scheduler`;
* everything the host feeds per step (block tables, lengths, tokens,
  sampling params) is replicated, and the sampled tokens come back
  replicated, so the scheduler never sees the mesh.

A 1-device mesh runs the identical code path (psum/gather compile away),
which is what keeps the conformance suite engine-shape-agnostic: the same
engine passes it on one device and on a forced multi-device CPU host
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``; CI runs that
variant on every PR).

The mesh is chosen automatically: the largest device count that divides
the model's effective kv heads, q heads, ff width (and padded vocab when
the unembedding is untied). Pass ``mesh=`` explicitly, or set a process
default with :func:`set_default_serving_mesh` /
:func:`serving_mesh_scope` (what ``launch/serve.py --mesh`` uses) —
the public engine signature stays mesh-free.
"""

from __future__ import annotations

from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.models.common import sample_tokens
from repro.models.lm import padded_vocab
from repro.parallel.axes import logical_to_spec
from repro.parallel.collectives import tensor_parallel
from repro.serving.kv_cache import write_prefill_pages
from repro.serving.metrics import phase
from repro.serving.scheduler import DecodeInputs, PrefillChunk, StepPlan

__all__ = [
    "ModelExecutor",
    "default_serving_mesh",
    "init_serving_params",
    "pick_tp",
    "serving_mesh_scope",
    "set_default_serving_mesh",
    "validate_serving_mesh",
]

# (L, P, page, KVH, Dh): only the head dim is sharded, so page ids and
# block-table entries mean the same thing on every shard
PAGE_SPEC = P(None, None, None, "model", None)
# int8 pools carry per-page-per-head scale arrays (L, P, page, KVH) — same
# head sharding, no Dh axis
SCALE_SPEC = P(None, None, None, "model")

_DEFAULT_MESH: Mesh | None = None


def set_default_serving_mesh(mesh: Mesh | None) -> None:
    """Process-wide default mesh for engines built without an explicit one
    (``launch/serve.py --mesh``). ``None`` restores auto-selection."""
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


@contextmanager
def serving_mesh_scope(mesh: Mesh | None):
    """Temporarily pin the default serving mesh (tests: force a 1-device
    mesh next to the auto-sharded one and compare outputs)."""
    global _DEFAULT_MESH
    prev = _DEFAULT_MESH
    _DEFAULT_MESH = mesh
    try:
        yield
    finally:
        _DEFAULT_MESH = prev


def _tp_dims(cfg) -> list[int]:
    """Tensor dims the mesh size must divide for this config."""
    # pure-SSM configs carry default head fields no layer ever uses —
    # only constrain on attention dims when attention layers exist
    dims = [] if cfg.family == "ssm" else [cfg.eff_kv_heads, cfg.eff_heads]
    if cfg.ssm_state:
        # SSM/hybrid: d_inner is ff-sharded and the state bank shards on
        # ssm_heads; keep both so pure-SSM configs never vacuously admit
        # any mesh size
        dims += [cfg.ssm_heads, cfg.d_inner]
    if cfg.d_ff:
        dims.append(cfg.d_ff)
    if not cfg.tie_embeddings:
        dims.append(padded_vocab(cfg))
    return [d for d in dims if d]


def pick_tp(cfg, num_devices: int | None = None) -> int:
    """Largest tensor-parallel degree <= the device count that divides every
    sharded dim (kv heads bound it in practice: pages shard along heads)."""
    n = num_devices if num_devices is not None else jax.device_count()
    dims = _tp_dims(cfg)
    tp = max(1, n)
    while tp > 1 and any(d % tp for d in dims):
        tp -= 1
    return tp


def default_serving_mesh(cfg) -> Mesh:
    if _DEFAULT_MESH is not None:
        return _DEFAULT_MESH
    return make_serving_mesh(pick_tp(cfg))


def _mesh_tp(mesh: Mesh) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))["model"]


def validate_serving_mesh(cfg, mesh: Mesh) -> int:
    """Check ``mesh`` can shard ``cfg`` (every TP dim divisible); returns
    the TP degree. Drivers call this ONCE up front so a bad explicit
    ``--mesh N`` fails fast in the main thread instead of crashing every
    worker as it builds its engine."""
    tp = _mesh_tp(mesh)
    bad = [d for d in _tp_dims(cfg) if d % tp]
    if bad:
        raise ValueError(
            f"serving mesh size {tp} does not divide sharded dims {bad} of "
            f"{cfg.name} (kv_heads={cfg.eff_kv_heads}, "
            f"heads={cfg.eff_heads}, d_ff={cfg.d_ff})"
        )
    return tp


def _serving_param_specs(model, mesh: Mesh, vocab_sharded: bool):
    """PartitionSpec tree for a params tree under the serving TP rules."""
    rules = {
        "heads": "model",
        "kv_heads": "model",
        "ff": "model",
        "ssm_heads": "model",
        "vocab": "model" if vocab_sharded else None,
    }
    is_leaf = lambda v: v is None or (
        isinstance(v, tuple)
        and all(isinstance(e, (str, type(None))) for e in v)
    )
    specs = jax.tree.map(
        lambda ax: logical_to_spec(ax, rules=rules, mesh=mesh),
        model.axes(), is_leaf=is_leaf,
    )
    # the token-embedding table is looked up by GLOBAL token id
    # (jnp.take), so it must stay replicated even when the (untied)
    # unembedding shards its vocab columns
    if "embed" in specs:
        specs["embed"] = P()
    return specs


def init_serving_params(cfg, key, mesh: Mesh | None = None):
    """Initialize a params tree directly in its serving shardings.

    ``jit`` of the model's ``init`` with the serving TP specs as
    ``out_shardings``: every shard is created on its own device, so a model
    whose weights do not fit one chip is never materialized whole on the
    first device. Multi-worker drivers (``launch/serve.py``) call this
    before spawning engines: every :class:`ModelExecutor` built from the
    returned tree sees leaves already carrying the target sharding, and its
    own ``device_put`` is then a no-op — all workers share ONE placed copy.
    """
    mesh = mesh if mesh is not None else default_serving_mesh(cfg)
    tp = validate_serving_mesh(cfg, mesh)
    model = build_model(cfg)
    specs = _serving_param_specs(model, mesh, (not cfg.tie_embeddings) and tp > 1)
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda v: isinstance(v, P),
    )
    return jax.jit(model.init, out_shardings=shardings)(key)


class ModelExecutor:
    """Owns params, page-pool device arrays and the jitted step functions.

    Stateless with respect to scheduling: it executes
    :class:`~repro.serving.scheduler.PrefillChunk` /
    :class:`~repro.serving.scheduler.DecodeInputs` work items and keeps
    device mirrors of the last decode batch so steady-state steps transfer
    nothing to the device.

    Each call is split into the step phases only the executor can tell
    apart (``serving/metrics.py``): ``pack`` (host inputs assembled and
    uploaded), ``dispatch`` (the jitted call, up to its return) and
    ``sync`` (the blocking readback of the sampled tokens).
    """

    def __init__(self, cfg, params, cache, *, max_len: int,
                 mesh: Mesh | None = None, attn_impl: str | None = None):
        self.cfg = cfg
        # serving defaults to "auto" (not the model-default "xla_chunked"):
        # on TPU every hot path — paged decode, chunked prefill, legacy
        # whole-prompt flash — dispatches its Pallas kernel per shard; on
        # CPU "auto" resolves to the identical XLA reference lowering
        self.model = build_model(cfg, attn_impl=attn_impl or "auto")
        self.cache = cache
        self.max_len = max_len
        self.nf = cfg.num_frontend_tokens if cfg.family == "vlm" else 0
        self.mesh = mesh if mesh is not None else default_serving_mesh(cfg)
        self.tp = validate_serving_mesh(cfg, self.mesh)
        self.vocab_sharded = (not cfg.tie_embeddings) and self.tp > 1
        self.param_specs = _serving_param_specs(
            self.model, self.mesh, self.vocab_sharded
        )
        self.params = self._place(params)

        self._decode_fns: dict[bool, object] = {}
        self._mixed_fns: dict[bool, object] = {}
        self._chunk_fn = None
        self._prefill_fns: dict[int, object] = {}
        self._verify_fns: dict[bool, object] = {}
        # device mirrors of the last decode batch, PACKED into one int32
        # and one f32 array (refreshed only when the scheduler reports a
        # composition change). Packing matters off-TPU: per-transfer
        # dispatch overhead dominates small-step serving, so a refresh is
        # two device_puts instead of nine, and a chunk rides in two more
        # instead of six (see ``_DI_COLS``).
        self._greedy_only = True
        self._di = self._df = None

    # ------------------------------------------------------------------
    # sharding
    # ------------------------------------------------------------------
    def _place(self, params):
        """Shard params + page pool onto the mesh (no-op layout on 1 dev).

        When the caller pre-placed the tree (:func:`init_serving_params`,
        the multi-worker path) every ``device_put`` here no-ops and all
        executors share one device copy of the weights."""
        if self.tp == 1:
            return params
        ns = lambda spec: NamedSharding(self.mesh, spec)
        placed = jax.tree.map(
            lambda arr, spec: jax.device_put(arr, ns(spec)),
            params, self.param_specs,
        )
        self.cache._reshard(
            {key: ns(spec) for key, spec in self._page_specs().items()}
        )
        return placed

    def _page_specs(self) -> dict:
        """Per-array PartitionSpecs for the cache's page dict (scale arrays
        drop the Dh axis but shard the same head dim)."""
        return {
            key: PAGE_SPEC if arr.ndim == 5 else SCALE_SPEC
            for key, arr in self.cache.pages.items()
        }

    def _smap(self, fn, in_specs, out_specs):
        # replication checking off: scatters and gathered logits are
        # replicated by construction, which the checker cannot prove
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def _tp_ctx(self):
        return tensor_parallel("model", vocab_sharded=self.vocab_sharded)

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    # Packed decode batch ``di`` (S, MP+6) int32: block table row, then
    # _DI_COLS-indexed columns [lens, active, tokens, top_ks, seeds, idx];
    # ``df`` (S, 2) f32: [temps, top_ps]. The jitted fns slice at static
    # offsets (MP is fixed per cache) and return an ADVANCED ``di`` —
    # lens/idx stepped, sampled tokens written back — so the steady-state
    # loop feeds device outputs straight into the next step.
    _DI_COLS = 6

    def _decode_fn(self, greedy_only: bool):
        """ONE dispatch per decode step: sharded model step + sampling
        fused, logits never leave the device (the vocab gather is an
        on-device collective). ``greedy_only`` is a host-known flag — the
        all-greedy compile pays a plain argmax and the per-row
        top-k/top-p/seeded sampler only costs when a sampled request is in
        flight. The advanced packed batch returns replicated and feeds the
        next step directly."""
        if greedy_only not in self._decode_fns:
            cfg = self.cfg

            def fn(params, pages, di, df):
                mp = di.shape[1] - self._DI_COLS
                bt, lens, active = di[:, :mp], di[:, mp], di[:, mp + 1]
                with self._tp_ctx():
                    pages, logits = self.model.decode_step_paged(
                        params, pages, bt, lens, di[:, mp + 2:mp + 3]
                    )
                    if greedy_only:
                        toks = jnp.argmax(
                            logits[..., :cfg.vocab_size], axis=-1
                        ).astype(jnp.int32)
                    else:
                        toks = sample_tokens(logits, df[:, 0], di[:, mp + 3],
                                             df[:, 1], di[:, mp + 4],
                                             di[:, mp + 5], cfg.vocab_size)
                di = di.at[:, mp].set(lens + active)
                di = di.at[:, mp + 2].set(toks)
                di = di.at[:, mp + 5].add(active)
                return pages, di, toks

            page_specs = self._page_specs()
            smapped = self._smap(
                fn,
                in_specs=(self.param_specs, page_specs) + (P(),) * 2,
                out_specs=(page_specs, P(), P()),
            )
            self._decode_fns[greedy_only] = jax.jit(
                smapped, donate_argnums=(1, 2)
            )
        return self._decode_fns[greedy_only]

    def refresh(self, inputs: DecodeInputs) -> None:
        """Mirror a freshly assembled decode batch to the device (two
        transfers: the packed int32 batch and the packed f32 sampling
        params)."""
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
        """Run one decode step. ``inputs`` refreshes the device mirrors
        (admission/eviction/page growth); None reuses last step's device
        outputs — the steady-state loop transfers nothing to the device.
        Returns the sampled token per slot, (S,) int32 on the host."""
        if inputs is not None:
            self.refresh(inputs)
        with phase("dispatch"):
            fn = self._decode_fn(self._greedy_only)
            pages, self._di, toks = fn(self.params, dict(self.cache.pages),
                                       self._di, self._df)
            self.cache.swap_pages(pages)
        with phase("sync"):
            return np.asarray(toks)

    # ------------------------------------------------------------------
    # fused mixed step (decode batch + one prefill chunk, one dispatch)
    # ------------------------------------------------------------------
    def _pack_chunk(self, chunk) -> tuple[jax.Array, jax.Array]:
        """Pack one prefill chunk's host state into two transfers:
        ``ci`` (MP+C+4,) int32 = [block-table row | padded tokens | start,
        valid, top_k, seed] and ``cf`` (2,) f32 = [temperature, top_p]."""
        with phase("pack"):
            sp = chunk.seq.request.sampling
            row = self.cache.block_tables[chunk.slot]
            mp, c = row.shape[0], chunk.tokens.shape[0]
            ci = np.empty(mp + c + 4, np.int32)
            ci[:mp] = row
            ci[mp:mp + c] = chunk.tokens
            ci[mp + c:] = (chunk.start, chunk.valid, sp.top_k,
                           chunk.seq.handle.seed)
            cf = np.array([sp.temperature, sp.top_p], np.float32)
            return jnp.asarray(ci), jnp.asarray(cf)

    def _mixed_fn(self, greedy_only: bool):
        """ONE dispatch per mixed step: every decode slot AND one prefill
        chunk run a single sharded model step + fused sampling over S+C
        single-token rows — the full-occupancy step the interleaved path's
        two dispatches approximate. Decode rows keep their exact decode
        semantics (same device-mirror feedback: sampled tokens / advanced
        lengths / advanced sample indices return replicated and feed the
        next step); the chunk contributes C rows sharing its slot's
        block-table row and one extra sampled token at index 0, meaningful
        only on the prompt's final chunk. ``greedy_only`` covers the chunk
        too — a sampled chunk (temperature > 0) selects the sampling
        compile, where greedy rows still reduce to argmax, so streams
        cannot depend on the compile chosen."""
        if greedy_only not in self._mixed_fns:
            cfg = self.cfg

            def fn(params, pages, di, df, ci, cf):
                s = di.shape[0]
                mp = di.shape[1] - self._DI_COLS
                c = ci.shape[0] - mp - 4
                bt, lens, active = di[:, :mp], di[:, mp], di[:, mp + 1]
                crow, ctoks = ci[:mp], ci[mp:mp + c]
                cstart, cvalid = ci[mp + c], ci[mp + c + 1]
                with self._tp_ctx():
                    # rows [0,S): decode slots at position = length (-1 when
                    # idle); rows [S,S+C): the chunk at start+i (-1 past valid)
                    cidx = jnp.arange(c, dtype=jnp.int32)
                    positions = jnp.concatenate([
                        jnp.where(active == 1, lens, -1),
                        jnp.where(cidx < cvalid, cstart + cidx, -1),
                    ]).astype(jnp.int32)
                    tables = jnp.concatenate([
                        bt, jnp.broadcast_to(crow, (c, mp)),
                    ])
                    pages, logits = self.model.mixed_step_paged(
                        params, pages, tables, positions,
                        jnp.concatenate([di[:, mp + 2:mp + 3],
                                         ctoks[:, None]]),
                        num_decode=s, chunk_valid=cvalid,
                    )  # logits (S+1, Vp): decode rows + the chunk's row
                    if greedy_only:
                        toks = jnp.argmax(
                            logits[..., :cfg.vocab_size], axis=-1
                        ).astype(jnp.int32)
                    else:
                        toks = sample_tokens(
                            logits,
                            jnp.concatenate([df[:, 0], cf[0][None]]),
                            jnp.concatenate([di[:, mp + 3],
                                             ci[mp + c + 2][None]]),
                            jnp.concatenate([df[:, 1], cf[1][None]]),
                            jnp.concatenate([di[:, mp + 4],
                                             ci[mp + c + 3][None]]),
                            jnp.concatenate([di[:, mp + 5],
                                             jnp.zeros((1,), jnp.int32)]),
                            cfg.vocab_size,
                        )
                dtoks = toks[:s]
                di = di.at[:, mp].set(lens + active)
                di = di.at[:, mp + 2].set(dtoks)
                di = di.at[:, mp + 5].add(active)
                return pages, di, dtoks, toks[s]

            page_specs = self._page_specs()
            smapped = self._smap(
                fn,
                in_specs=(self.param_specs, page_specs) + (P(),) * 4,
                out_specs=(page_specs, P(), P(), P()),
            )
            self._mixed_fns[greedy_only] = jax.jit(
                smapped, donate_argnums=(1, 2)
            )
        return self._mixed_fns[greedy_only]

    def step(self, plan: StepPlan) -> tuple[np.ndarray | None, int | None]:
        """Execute one step plan. Returns ``(decode_toks, chunk_tok)``:
        the sampled token per slot ((S,) int32 on the host, None when the
        plan had no decode rows) and the chunk's sampled first token (None
        when the plan had no chunk; meaningful only on a final chunk).

        Degenerate plans route to the specialized dispatches — chunk-only
        (cold start / post-burst refill) runs the chunk kernel without S
        dead decode rows, decode-only (steady state between prefills) runs
        the existing decode step with its zero-transfer device mirrors."""
        chunk = plan.chunk
        if not plan.decode_slots:
            ctok = self.prefill_chunk(chunk) if chunk is not None else None
            return None, ctok
        if chunk is None:
            return self.decode(plan.decode), None
        if plan.decode is not None:
            self.refresh(plan.decode)
        ci, cf = self._pack_chunk(chunk)
        with phase("dispatch"):
            sp = chunk.seq.request.sampling
            fn = self._mixed_fn(self._greedy_only and sp.temperature <= 0.0)
            pages, self._di, toks, ctok = fn(
                self.params, dict(self.cache.pages), self._di, self._df,
                ci, cf,
            )
            self.cache.swap_pages(pages)
        with phase("sync"):
            return np.asarray(toks), int(ctok)

    # ------------------------------------------------------------------
    # speculative verify (one bundle = one fused dispatch)
    # ------------------------------------------------------------------
    # Packed bundle ``vi`` (MP+W+5,) int32 = [block-table row | padded
    # tokens | start, valid, top_k, seed, idx0] and ``vf`` (2,) f32 =
    # [temperature, top_p] — the chunk packing plus the base token index,
    # which keys each row's sample.
    def _verify_fn(self, greedy_only: bool):
        """ONE dispatch scores a whole speculation bundle: the sharded
        verify forward (``models/lm.py::verify_step_paged`` — a k+1-row
        chunk over the slot's own block table) plus per-row sampling,
        fused so logits never leave the device. Row j samples with the
        request's ``(seed, idx0 + j)`` key — the SAME key a sequential
        decode loop would use for token index ``idx0 + j`` — which is the
        whole acceptance argument: where the drafted prefix matches what
        sequential decoding would have produced, the logits match, the
        keys match, and therefore the samples match (greedy rows are a
        plain argmax, so greedy streams are byte-identical by
        construction). ``greedy_only`` picks the argmax compile exactly
        like the decode/mixed steps."""
        if greedy_only not in self._verify_fns:
            cfg = self.cfg
            mp = self.cache.block_tables.shape[1]

            def fn(params, pages, vi, vf):
                w = vi.shape[0] - mp - 5
                row, tokens = vi[:mp], vi[mp:mp + w]
                start, valid = vi[mp + w], vi[mp + w + 1]
                with self._tp_ctx():
                    pages, logits = self.model.verify_step_paged(
                        params, pages, row, tokens, start, valid,
                    )  # (W, Vp): row j scores token index idx0 + j
                    if greedy_only:
                        toks = jnp.argmax(
                            logits[..., :cfg.vocab_size], axis=-1
                        ).astype(jnp.int32)
                    else:
                        ones = jnp.ones((w,), jnp.float32)
                        toks = sample_tokens(
                            logits, vf[0] * ones,
                            jnp.broadcast_to(vi[mp + w + 2], (w,)),
                            vf[1] * ones,
                            jnp.broadcast_to(vi[mp + w + 3], (w,)),
                            vi[mp + w + 4] + jnp.arange(w, dtype=jnp.int32),
                            cfg.vocab_size,
                        )
                return pages, toks

            page_specs = self._page_specs()
            smapped = self._smap(
                fn,
                in_specs=(self.param_specs, page_specs) + (P(),) * 2,
                out_specs=(page_specs, P()),
            )
            self._verify_fns[greedy_only] = jax.jit(
                smapped, donate_argnums=(1,)
            )
        return self._verify_fns[greedy_only]

    def verify(self, bundle) -> np.ndarray:
        """Dispatch one speculation bundle (``scheduler.SpecBundle``).
        Returns the sampled token per bundle row, (W,) int32 on the host
        — row 0 is the true next token, row j (j < valid) the true token
        IF rows < j were all accepted; rows past ``valid`` are garbage
        the engine ignores. The dispatch also scattered the bundle's k+1
        candidate KV positions; the engine commits the accepted prefix by
        setting the slot's length (rollback = rewind, nothing else)."""
        sp = bundle.seq.request.sampling
        with phase("pack"):
            row = self.cache.block_tables[bundle.slot]
            mp, w = row.shape[0], bundle.tokens.shape[0]
            vi = np.empty(mp + w + 5, np.int32)
            vi[:mp] = row
            vi[mp:mp + w] = bundle.tokens
            vi[mp + w:] = (bundle.start, bundle.valid, sp.top_k,
                           bundle.seq.handle.seed, len(bundle.seq.tokens))
            vf = np.array([sp.temperature, sp.top_p], np.float32)
            vi, vf = jnp.asarray(vi), jnp.asarray(vf)
        with phase("dispatch"):
            fn = self._verify_fn(sp.temperature <= 0.0)
            pages, toks = fn(self.params, dict(self.cache.pages), vi, vf)
            self.cache.swap_pages(pages)
        with phase("sync"):
            return np.asarray(toks)

    # ------------------------------------------------------------------
    # chunked prefill
    # ------------------------------------------------------------------
    def _chunk_prefill_fn(self):
        """ONE jitted function (static chunk shape) covers every prompt
        length — sharded chunk forward + page scatter + sample fused. The
        sampled token is only meaningful on a prompt's final chunk.

        Under the mesh this runs per shard exactly like decode: the chunk
        attention (Pallas kernel on TPU, XLA ref elsewhere — see
        ``ops.paged_prefill_attention``) sees the local kv-head slice of
        the page pool with the block-table row replicated."""
        if self._chunk_fn is None:
            mp = self.cache.block_tables.shape[1]

            def fn(params, pages, ci, cf):
                c = ci.shape[0] - mp - 4
                row, tokens = ci[:mp], ci[mp:mp + c]
                start, valid = ci[mp + c], ci[mp + c + 1]
                with self._tp_ctx():
                    pages, logits = self.model.prefill_chunk(
                        params, pages, row, tokens, start, valid,
                    )
                    tok = sample_tokens(
                        logits[None], cf[0][None], ci[mp + c + 2][None],
                        cf[1][None], ci[mp + c + 3][None],
                        jnp.zeros((1,), jnp.int32), self.cfg.vocab_size,
                    )
                return pages, tok[0]

            page_specs = self._page_specs()
            smapped = self._smap(
                fn,
                in_specs=(self.param_specs, page_specs) + (P(),) * 2,
                out_specs=(page_specs, P()),
            )
            self._chunk_fn = jax.jit(smapped, donate_argnums=(1,))
        return self._chunk_fn

    def prefill_chunk(self, work: PrefillChunk) -> int:
        """Dispatch one chunk; returns the sampled first token (meaningful
        only when this was the prompt's final chunk)."""
        ci, cf = self._pack_chunk(work)
        with phase("dispatch"):
            pages, tok = self._chunk_prefill_fn()(
                self.params, dict(self.cache.pages), ci, cf
            )
            self.cache.swap_pages(pages)
        with phase("sync"):
            return int(tok)

    # ------------------------------------------------------------------
    # legacy whole-prompt prefill (prefill_chunk=None / vlm)
    # ------------------------------------------------------------------
    def _bucket(self, plen: int) -> int:
        b = 16
        while b < plen:
            b *= 2
        return min(b, max(self.max_len - self.nf, 1))

    def _prefill_fn(self, bucket: int):
        """Whole-prompt path: ONE dispatch per admission — sharded prefill
        forward + page scatter + first-token sample, jitted per
        prompt-length bucket."""
        if bucket not in self._prefill_fns:
            s_total = self.nf + bucket

            def fn(params, batch, idx, pages, row, valid_len,
                   temp, tk, tp, rseed):
                with self._tp_ctx():
                    cache, logits = self.model.prefill(
                        params, batch, s_total, logits_index=idx
                    )
                    # cache["k"] is (L, 1, S, KVH/tp, Dh): the local head
                    # slice scatters into the local page shard — positions
                    # and page ids are shard-invariant
                    pages = write_prefill_pages(
                        pages, cache["k"][:, 0], cache["v"][:, 0],
                        row, valid_len,
                    )
                    tok = sample_tokens(
                        logits, temp[None], tk[None], tp[None], rseed[None],
                        jnp.zeros((1,), jnp.int32), self.cfg.vocab_size,
                    )
                return pages, tok[0]

            page_specs = self._page_specs()
            smapped = self._smap(
                fn,
                in_specs=(self.param_specs, P(), P(), page_specs)
                + (P(),) * 6,
                out_specs=(page_specs, P()),
            )
            self._prefill_fns[bucket] = jax.jit(
                smapped, donate_argnums=(3,)
            )
        return self._prefill_fns[bucket]

    def prefill_whole(self, request, seed: int, slot: int) -> int:
        """Prefill a whole prompt into its pages; returns the first token."""
        plen = len(request.prompt)
        ctx = self.nf + plen
        bucket = self._bucket(plen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = request.prompt
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.family == "vlm":
            batch["vision_embeds"] = jnp.zeros(
                (1, self.nf, self.cfg.d_model), jnp.dtype(self.cfg.dtype)
            )
        sp = request.sampling
        with phase("dispatch"):
            pages, tok = self._prefill_fn(bucket)(
                self.params, batch, jnp.asarray(ctx - 1, jnp.int32),
                dict(self.cache.pages),
                self.cache.device_row(slot),
                jnp.asarray(ctx, jnp.int32),
                jnp.asarray(sp.temperature, jnp.float32),
                jnp.asarray(sp.top_k, jnp.int32),
                jnp.asarray(sp.top_p, jnp.float32),
                jnp.asarray(seed, jnp.int32),
            )
            self.cache.swap_pages(pages)
        with phase("sync"):
            return int(tok)
