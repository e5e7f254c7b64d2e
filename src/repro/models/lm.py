"""Unified decoder-only LM covering dense / moe / vlm / ssm / hybrid families.

One class, five lowered entry points:
  * ``loss_fn(params, batch)``       — training forward + chunked CE loss
  * ``prefill(params, batch, max_len)`` — full-seq forward, returns KV/SSM cache
  * ``decode_step(params, cache, tokens)`` — one token with cache update
  * ``decode_step_paged(params, pages, ...)`` — one token per serving slot
    against the shared paged KV pool (continuous batching)
  * ``prefill_chunk(params, pages, ...)`` — one fixed-size prompt chunk of
    one sequence scattered into its page set (chunked prefill)

The layer stack is a ``lax.scan`` over stacked per-layer params (compile time
O(1) in depth) with configurable ``jax.checkpoint`` policy; the paged steps
carry the stacked page pool through that scan and write it in place. Vocab
is padded to a multiple of 256 for clean TP sharding (padded logits are
masked to -inf in the loss — exact math, standard Megatron practice).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (
    ParamSpec,
    abstract_params,
    init_params,
    param_axes,
    rms_norm,
    swiglu,
)
from repro.parallel import constrain
from repro.parallel.collectives import all_gather_logits

VOCAB_PAD_MULTIPLE = 256


def mlp_param_specs(cfg: ModelConfig, stacked: int | None = None) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    pre = (stacked,) if stacked else ()
    pax = ("stack",) if stacked else ()
    return {
        "w_gate": ParamSpec(pre + (d, f), pax + ("embed", "ff")),
        "w_up": ParamSpec(pre + (d, f), pax + ("embed", "ff")),
        "w_down": ParamSpec(pre + (f, d), pax + ("ff", "embed")),
    }


def padded_vocab(cfg: ModelConfig) -> int:
    v = cfg.vocab_size
    return ((v + VOCAB_PAD_MULTIPLE - 1) // VOCAB_PAD_MULTIPLE) * VOCAB_PAD_MULTIPLE


def chunked_cross_entropy(
    x: jax.Array,        # (B, S, D) final hidden states
    w_out: jax.Array,    # (D, Vp)
    targets: jax.Array,  # (B, S) int32
    real_vocab: int,
    chunk: int = 1024,
) -> jax.Array:
    """Mean next-token CE without materializing (B,S,V) logits.

    Scans sequence chunks; each chunk is wrapped in jax.checkpoint so the
    backward pass recomputes its logits instead of saving them.
    """
    b, s, d = x.shape
    vp = w_out.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    xc = jnp.moveaxis(x.reshape(b, nc, chunk, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(b, nc, chunk), 1, 0)

    vocab_mask = (jnp.arange(vp) < real_vocab) if real_vocab != vp else None

    @jax.checkpoint
    def one(x_chunk, t_chunk):
        logits = jnp.einsum(
            "bsd,dv->bsv", x_chunk, w_out, preferred_element_type=jnp.float32
        )
        logits = constrain(logits, "batch", "seq", "vocab")
        if vocab_mask is not None:
            logits = jnp.where(vocab_mask[None, None, :], logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_chunk[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - tgt)

    def body(tot, inp):
        x_chunk, t_chunk = inp
        return tot + one(x_chunk, t_chunk), ()

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, tc))
    return total / (b * s)


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "names":
        # Megatron-style: save the post-all-reduce block outputs (tagged
        # with checkpoint_name below) so the backward recompute never
        # re-runs the TP collectives — trades 2 saved (B,S,D) tensors per
        # layer for ~1/3 of the activation all-reduce bytes
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "mlp_out"
            ),
        )
    if policy == "nothing" or policy.startswith("group"):
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(f"unknown remat policy {policy!r}")


def scan_layers(body, carry, layers, policy: str):
    """Scan a layer stack with the configured checkpointing strategy.

    * "nothing"/"dots"/"none": plain scan of a (possibly remat'd) body —
      the scan still saves the carry at EVERY layer (L x microbatch bytes).
    * "groupG" (e.g. "group8"): sqrt-L checkpointing — outer scan over
      blocks of G layers, each block remat'd as a unit, so only L/G carries
      are saved and the block recomputes its layers in backward. Trades
      ~1 extra forward of the block for a G-fold cut in saved activations.
    """
    if policy.startswith("group"):
        spec = policy[len("group"):]
        inner_policy = "names" if spec.endswith("names") else "nothing"
        spec = spec.removesuffix("names")
        g = int(spec or 8)
        first = jax.tree.leaves(layers)[0]
        n_layers = first.shape[0]
        if n_layers % g != 0:
            g = next(d for d in range(g, 0, -1) if n_layers % d == 0)
        ng = n_layers // g
        grouped = jax.tree.map(
            lambda a: a.reshape((ng, g) + a.shape[1:]), layers
        )
        # two-level checkpointing: the outer scan saves only group-boundary
        # carries; each layer inside is ALSO remat'd so the group backward
        # holds one layer's internals at a time, not the whole group's
        inner = _remat(body, inner_policy)

        def group_body(c, pg):
            c2, _ = jax.lax.scan(inner, c, pg)
            return c2, ()

        return jax.lax.scan(_remat(group_body, "nothing"), carry, grouped)
    return jax.lax.scan(_remat(body, policy), carry, layers)


class DecoderLM:
    """Families: dense, moe, vlm, ssm, hybrid."""

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        attn_impl: str = "xla_chunked",
        ssd_impl: str = "xla_chunked",
    ):
        assert not cfg.is_encoder_decoder
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.ssd_impl = ssd_impl

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def param_specs(self) -> dict:
        cfg = self.cfg
        L, D = cfg.num_layers, cfg.d_model
        vp = padded_vocab(cfg)
        specs: dict[str, Any] = {
            "embed": ParamSpec((vp, D), ("vocab", None), init="embed", scale=0.02),
            "final_norm": ParamSpec((D,), (None,), init="ones"),
        }
        if not cfg.tie_embeddings:
            specs["unembed"] = ParamSpec((D, vp), (None, "vocab"))
        if cfg.family == "vlm":
            specs["vision_proj"] = ParamSpec((D, D), ("embed", None))

        if cfg.family == "ssm":
            specs["layers"] = {
                "ln": ParamSpec((L, D), ("stack", None), init="ones"),
                "mamba": ssm_mod.mamba_param_specs(cfg, stacked=L),
            }
        elif cfg.family == "hybrid":
            specs["layers"] = {
                "ln": ParamSpec((L, D), ("stack", None), init="ones"),
                "mamba": ssm_mod.mamba_param_specs(cfg, stacked=L),
            }
            specs["shared"] = {
                "ln1": ParamSpec((D,), (None,), init="ones"),
                "attn": attn.attn_param_specs(cfg),
                "ln2": ParamSpec((D,), (None,), init="ones"),
                "mlp": mlp_param_specs(cfg),
            }
        else:
            layer: dict[str, Any] = {
                "ln1": ParamSpec((L, D), ("stack", None), init="ones"),
                "attn": attn.attn_param_specs(cfg, stacked=L),
                "ln2": ParamSpec((L, D), ("stack", None), init="ones"),
            }
            if cfg.family == "moe":
                layer["moe"] = moe_mod.moe_param_specs(cfg, stacked=L)
            else:
                layer["mlp"] = mlp_param_specs(cfg, stacked=L)
            specs["layers"] = layer
        return specs

    def init(self, key):
        return init_params(self.param_specs(), key, self.cfg.dtype)

    def abstract(self):
        return abstract_params(self.param_specs(), self.cfg.dtype)

    def axes(self):
        return param_axes(self.param_specs())

    def _unembed_weight(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["unembed"]

    # ------------------------------------------------------------------
    # embedding / inputs
    # ------------------------------------------------------------------
    def embed_inputs(self, params, batch) -> jax.Array:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
        if self.cfg.family == "vlm":
            vis = batch["vision_embeds"].astype(x.dtype)
            vis = jnp.einsum("bfd,de->bfe", vis, params["vision_proj"])
            x = jnp.concatenate([vis, x], axis=1)
        return constrain(x, "batch", "seq", None)

    # ------------------------------------------------------------------
    # layer stacks (train mode)
    # ------------------------------------------------------------------
    def _dense_layer(self, pl, x, aux, positions):
        cfg = self.cfg
        h = rms_norm(x, pl["ln1"], cfg.norm_eps)
        h = attn.self_attention(
            pl["attn"], h, cfg, positions=positions, attn_impl=self.attn_impl
        )
        h = checkpoint_name(h, "attn_out")  # post-AR (see _remat "names")
        x = x + h
        h = rms_norm(x, pl["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            h, a = moe_mod.moe_block(pl["moe"], h, cfg)
            aux = aux + a
        else:
            h = swiglu(h, pl["mlp"]["w_gate"], pl["mlp"]["w_up"], pl["mlp"]["w_down"],
                       constrain=lambda t, *ax: constrain(t, *ax))
        h = checkpoint_name(h, "mlp_out")  # post-AR
        x = constrain(x + h, "batch", "seq", None)
        return x, aux

    def _shared_attn_block(self, shared, x, positions):
        cfg = self.cfg
        h = rms_norm(x, shared["ln1"], cfg.norm_eps)
        h = attn.self_attention(
            shared["attn"], h, cfg, positions=positions, attn_impl=self.attn_impl
        )
        x = x + h
        h = rms_norm(x, shared["ln2"], cfg.norm_eps)
        h = swiglu(h, shared["mlp"]["w_gate"], shared["mlp"]["w_up"],
                   shared["mlp"]["w_down"],
                   constrain=lambda t, *ax: constrain(t, *ax))
        return x + h

    def backbone_train(self, params, x) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        positions = jnp.arange(x.shape[1])
        aux0 = jnp.zeros((), jnp.float32)
        policy = cfg.remat_policy

        if cfg.family in ("dense", "moe", "vlm"):
            def body(carry, pl):
                x, aux = carry
                x, aux = self._dense_layer(pl, x, aux, positions)
                return (x, aux), ()
            (x, aux), _ = scan_layers(body, (x, aux0), params["layers"], policy)
            return x, aux

        if cfg.family == "ssm":
            def body(carry, pl):
                x, aux = carry
                h = rms_norm(x, pl["ln"], cfg.norm_eps)
                h = ssm_mod.mamba_block(pl["mamba"], h, cfg, ssd_impl=self.ssd_impl)
                x = constrain(x + h, "batch", "seq", None)
                return (x, aux), ()
            (x, aux), _ = scan_layers(body, (x, aux0), params["layers"], policy)
            return x, aux

        if cfg.family == "hybrid":
            g = cfg.num_layers // cfg.attn_every
            grouped = jax.tree.map(
                lambda a: a.reshape((g, cfg.attn_every) + a.shape[1:]), params["layers"]
            )
            shared = params["shared"]

            def group_body(carry, pg):
                x, aux = carry
                x = self._shared_attn_block(shared, x, positions)

                def mbody(xc, pl):
                    h = rms_norm(xc, pl["ln"], cfg.norm_eps)
                    h = ssm_mod.mamba_block(pl["mamba"], h, cfg, ssd_impl=self.ssd_impl)
                    return constrain(xc + h, "batch", "seq", None), ()

                x, _ = jax.lax.scan(mbody, x, pg)
                return (x, aux), ()

            (x, aux), _ = jax.lax.scan(_remat(group_body, policy), (x, aux0), grouped)
            return x, aux

        raise ValueError(cfg.family)

    # ------------------------------------------------------------------
    # training loss
    # ------------------------------------------------------------------
    def loss_fn(self, params, batch) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x = self.embed_inputs(params, batch)
        x, aux = self.backbone_train(params, x)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.family == "vlm":
            x = x[:, batch["vision_embeds"].shape[1]:, :]
        ce = chunked_cross_entropy(
            x, self._unembed_weight(params), batch["targets"], cfg.vocab_size
        )
        loss = ce + (0.01 * aux if cfg.family == "moe" else 0.0)
        return loss, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------------
    # cache
    # ------------------------------------------------------------------
    def cache_struct(self, batch: int, max_len: int, abstract: bool):
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        L = cfg.num_layers

        def arr(shape, dtype):
            return (
                jax.ShapeDtypeStruct(shape, dtype)
                if abstract
                else jnp.zeros(shape, dtype)
            )

        pos = arr((), jnp.int32)
        if cfg.family in ("dense", "moe", "vlm"):
            kv = (L, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
            return {"k": arr(kv, dt), "v": arr(kv, dt), "pos": pos}
        if cfg.family == "ssm":
            mc = ssm_mod.init_mamba_cache(cfg, batch, dt, abstract=True)
            stacked = {
                k: arr((L,) + tuple(v.shape), v.dtype) for k, v in mc.items()
            }
            return {"mamba": stacked, "pos": pos}
        if cfg.family == "hybrid":
            g = cfg.num_layers // cfg.attn_every
            mc = ssm_mod.init_mamba_cache(cfg, batch, dt, abstract=True)
            stacked = {
                k: arr((L,) + tuple(v.shape), v.dtype) for k, v in mc.items()
            }
            kv = (g, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
            return {
                "mamba": stacked,
                "shared_k": arr(kv, dt),
                "shared_v": arr(kv, dt),
                "pos": pos,
            }
        raise ValueError(cfg.family)

    def init_cache(self, batch: int, max_len: int):
        return self.cache_struct(batch, max_len, abstract=False)

    def abstract_cache(self, batch: int, max_len: int):
        return self.cache_struct(batch, max_len, abstract=True)

    def cache_axes(self):
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            kv = ("stack", "cache_batch", "cache_seq", "kv_heads", "head_dim")
            return {"k": kv, "v": kv, "pos": None}
        mam = {
            k: ("stack",) + tuple(v) for k, v in ssm_mod.MAMBA_CACHE_AXES.items()
        }
        if cfg.family == "ssm":
            return {"mamba": mam, "pos": None}
        kv = ("stack", "cache_batch", "cache_seq", "kv_heads", "head_dim")
        return {"mamba": mam, "shared_k": kv, "shared_v": kv, "pos": None}

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def prefill(self, params, batch, max_len: int, *, logits_index=None):
        """Full-sequence forward; returns (cache, one position's logits).

        ``logits_index`` (traced scalar ok) selects which position's logits to
        return — the continuous batcher right-pads prompts to a shape bucket,
        so the last *real* token is not the last padded position. Default:
        the final position (lockstep behaviour).
        """
        cfg = self.cfg
        x = self.embed_inputs(params, batch)
        b, s, _ = x.shape
        positions = jnp.arange(s)
        pad = max_len - s

        def pad_kv(k):
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            # shard into the cache layout INSIDE the layer scan — otherwise
            # the stacked (L, B, Smax, KVH, Dh) output materializes with
            # batch-only sharding before the final reshard (GiBs per chip)
            return constrain(k, "cache_batch", "cache_seq", "kv_heads", "head_dim")

        if cfg.family in ("dense", "moe", "vlm"):
            def body(carry, pl):
                x, aux = carry
                h = rms_norm(x, pl["ln1"], cfg.norm_eps)
                h, (k, v) = attn.self_attention_with_cache_write(
                    pl["attn"], h, cfg, positions=positions, attn_impl=self.attn_impl
                )
                x = x + h
                h = rms_norm(x, pl["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    h, a = moe_mod.moe_block(pl["moe"], h, cfg)
                    aux = aux + a
                else:
                    h = swiglu(h, pl["mlp"]["w_gate"], pl["mlp"]["w_up"],
                               pl["mlp"]["w_down"])
                x = constrain(x + h, "batch", "seq", None)
                return (x, aux), {"k": pad_kv(k), "v": pad_kv(v)}

            (x, _), kv = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)), params["layers"]
            )
            cache = {"k": kv["k"], "v": kv["v"], "pos": jnp.asarray(s, jnp.int32)}

        elif cfg.family == "ssm":
            def body(x, pl):
                h = rms_norm(x, pl["ln"], cfg.norm_eps)
                h, mc = ssm_mod.mamba_block(
                    pl["mamba"], h, cfg, ssd_impl=self.ssd_impl, return_cache=True
                )
                return constrain(x + h, "batch", "seq", None), mc

            x, mam = jax.lax.scan(body, x, params["layers"])
            cache = {"mamba": mam, "pos": jnp.asarray(s, jnp.int32)}

        elif cfg.family == "hybrid":
            g = cfg.num_layers // cfg.attn_every
            grouped = jax.tree.map(
                lambda a: a.reshape((g, cfg.attn_every) + a.shape[1:]),
                params["layers"],
            )
            shared = params["shared"]

            def group_body(x, pg):
                h = rms_norm(x, shared["ln1"], cfg.norm_eps)
                h, (k, v) = attn.self_attention_with_cache_write(
                    shared["attn"], h, cfg, positions=positions,
                    attn_impl=self.attn_impl,
                )
                x = x + h
                h = rms_norm(x, shared["ln2"], cfg.norm_eps)
                h = swiglu(h, shared["mlp"]["w_gate"], shared["mlp"]["w_up"],
                           shared["mlp"]["w_down"])
                x = x + h

                def mbody(xc, pl):
                    hh = rms_norm(xc, pl["ln"], cfg.norm_eps)
                    hh, mc = ssm_mod.mamba_block(
                        pl["mamba"], hh, cfg, ssd_impl=self.ssd_impl,
                        return_cache=True,
                    )
                    return constrain(xc + hh, "batch", "seq", None), mc

                x, mcs = jax.lax.scan(mbody, x, pg)
                return x, {"kv": {"k": pad_kv(k), "v": pad_kv(v)}, "mamba": mcs}

            x, ys = jax.lax.scan(group_body, x, grouped)
            mam = jax.tree.map(
                lambda a: a.reshape((cfg.num_layers,) + a.shape[2:]), ys["mamba"]
            )
            cache = {
                "mamba": mam,
                "shared_k": ys["kv"]["k"],
                "shared_v": ys["kv"]["v"],
                "pos": jnp.asarray(s, jnp.int32),
            }
        else:
            raise ValueError(cfg.family)

        if logits_index is None:
            x = x[:, -1:, :]
        else:
            x = jax.lax.dynamic_slice_in_dim(x, logits_index, 1, axis=1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[:, 0]
        return cache, logits

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def decode_step(self, params, cache, tokens):
        """tokens (B, 1) -> (new_cache, logits (B, Vp) f32)."""
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)  # (B,1,D)
        pos = cache["pos"]

        if cfg.family in ("dense", "moe", "vlm"):
            def body(x, inp):
                pl, cl = inp
                h = rms_norm(x, pl["ln1"], cfg.norm_eps)
                h, new_cl = attn.decode_self_attention(pl["attn"], h, cl, pos, cfg)
                x = x + h
                h = rms_norm(x, pl["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    h, _ = moe_mod.moe_block(pl["moe"], h, cfg)
                else:
                    h = swiglu(h, pl["mlp"]["w_gate"], pl["mlp"]["w_up"],
                               pl["mlp"]["w_down"])
                return x + h, new_cl

            x, kv = jax.lax.scan(
                body, x, (params["layers"], {"k": cache["k"], "v": cache["v"]})
            )
            new_cache = {"k": kv["k"], "v": kv["v"], "pos": pos + 1}

        elif cfg.family == "ssm":
            def body(x, inp):
                pl, cl = inp
                h = rms_norm(x, pl["ln"], cfg.norm_eps)
                h, new_cl = ssm_mod.mamba_decode(
                    pl["mamba"], h, cl, cfg, ssd_impl=self.ssd_impl
                )
                return x + h, new_cl

            x, mam = jax.lax.scan(body, x, (params["layers"], cache["mamba"]))
            new_cache = {"mamba": mam, "pos": pos + 1}

        elif cfg.family == "hybrid":
            g = cfg.num_layers // cfg.attn_every
            grouped = jax.tree.map(
                lambda a: a.reshape((g, cfg.attn_every) + a.shape[1:]),
                params["layers"],
            )
            gmam = jax.tree.map(
                lambda a: a.reshape((g, cfg.attn_every) + a.shape[1:]),
                cache["mamba"],
            )
            shared = params["shared"]

            def group_body(x, inp):
                pg, mcg, kc, vc = inp
                h = rms_norm(x, shared["ln1"], cfg.norm_eps)
                h, new_kv = attn.decode_self_attention(
                    shared["attn"], h, {"k": kc, "v": vc}, pos, cfg
                )
                x = x + h
                h = rms_norm(x, shared["ln2"], cfg.norm_eps)
                x = x + swiglu(h, shared["mlp"]["w_gate"], shared["mlp"]["w_up"],
                               shared["mlp"]["w_down"])

                def mbody(xc, inp2):
                    pl, cl = inp2
                    hh = rms_norm(xc, pl["ln"], cfg.norm_eps)
                    hh, new_cl = ssm_mod.mamba_decode(
                        pl["mamba"], hh, cl, cfg, ssd_impl=self.ssd_impl
                    )
                    return xc + hh, new_cl

                x, new_mcs = jax.lax.scan(mbody, x, (pg, mcg))
                return x, {"kv": new_kv, "mamba": new_mcs}

            x, ys = jax.lax.scan(
                group_body, x, (grouped, gmam, cache["shared_k"], cache["shared_v"])
            )
            mam = jax.tree.map(
                lambda a: a.reshape((cfg.num_layers,) + a.shape[2:]), ys["mamba"]
            )
            new_cache = {
                "mamba": mam,
                "shared_k": ys["kv"]["k"],
                "shared_v": ys["kv"]["v"],
                "pos": pos + 1,
            }
        else:
            raise ValueError(cfg.family)

        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[:, 0]
        return new_cache, logits

    # ------------------------------------------------------------------
    # paged decode (continuous batching)
    # ------------------------------------------------------------------
    def _paged_layers(self, params, pages, x, attend):
        """The dense layer stack over the paged pool. The stacked pool
        rides the layer scan's CARRY (not its xs/ys), so each layer's
        ``attend(p_attn, h, pages, layer) -> (h, pages)`` writes its new
        rows in place at ``(layer, page, offset)`` and its kernel reads the
        stacked pool at that layer: no per-layer slice, re-stacked pool or
        whole-pool copy exists, and a donated pool comes back aliased.
        Returns (x, pages)."""
        cfg = self.cfg

        def body(carry, inp):
            x, pages = carry
            pl, layer = inp
            h = rms_norm(x, pl["ln1"], cfg.norm_eps)
            h, pages = attend(pl["attn"], h, pages, layer)
            x = x + h
            h = rms_norm(x, pl["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                h, _ = moe_mod.moe_block(pl["moe"], h, cfg)
            else:
                h = swiglu(h, pl["mlp"]["w_gate"], pl["mlp"]["w_up"],
                           pl["mlp"]["w_down"])
            return (x + h, pages), ()

        layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        (x, pages), _ = jax.lax.scan(
            body, (x, dict(pages)), (params["layers"], layers)
        )
        return x, pages

    def decode_step_paged(self, params, pages, block_tables, lengths, tokens):
        """One token per in-flight slot against the paged KV pool.

        pages: {"kv": (L,P,page,KVH*2*Dh)} — the shared page pool, stacked
        over layers and lane-dense (``serving/kv_cache.py``), plus
        "kv_scale" when int8. block_tables (S, MP) int32, lengths (S,)
        int32 (tokens already cached per slot; idle slots are 0), tokens
        (S, 1) int32. Returns (new_pages, logits (S, Vp) f32). Shapes are
        static across admissions/evictions, so the jitted step never
        recompiles.
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "moe", "vlm"), cfg.family
        x = jnp.take(params["embed"], tokens, axis=0)  # (S,1,D)

        def attend(p, h, pages, layer):
            return attn.decode_self_attention_paged(
                p, h, pages, layer, block_tables, lengths, cfg,
                attn_impl=self.attn_impl,
            )

        x, new_pages = self._paged_layers(params, pages, x, attend)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        # column-parallel unembed under TP serving: gather the vocab shards
        # so sampling sees the full distribution (identity when unsharded)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[:, 0]
        return new_pages, logits

    def mixed_step_paged(self, params, pages, block_tables, positions,
                         tokens, *, num_decode, chunk_valid):
        """Fused mixed step: ``num_decode`` decode rows plus one prefill
        chunk's rows in ONE forward pass over the paged KV pool.

        tokens (R, 1) int32 with R = num_decode + C: rows ``[0,
        num_decode)`` are the decode slots (their usual fixed width), rows
        ``[num_decode, R)`` are the chunk. block_tables (R, MP) int32 gives
        every row its own table (chunk rows repeat the chunk slot's row);
        positions (R,) int32 is each row's absolute position, -1 for dead
        rows (idle slots, chunk padding). ``num_decode`` is static;
        ``chunk_valid`` (scalar int32) selects the chunk's sampling row.

        Returns (new_pages, logits (num_decode + 1, Vp) f32): one logits
        row per decode slot plus the chunk's row ``chunk_valid - 1`` (the
        first-token sampling position — meaningful on the prompt's final
        chunk, garbage and ignored before that). Unembedding only touches
        those num_decode + 1 rows, so the fused step pays the chunk's extra
        rows in attention/MLP but not in the vocab projection.
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "moe"), cfg.family
        x = jnp.take(params["embed"], tokens, axis=0)  # (R,1,D)

        def attend(p, h, pages, layer):
            return attn.mixed_step_attention_paged(
                p, h, pages, layer, block_tables, positions, cfg,
                attn_impl=self.attn_impl, num_decode=num_decode,
            )

        x, new_pages = self._paged_layers(params, pages, x, attend)
        # decode rows + the chunk's sampling row, then ONE unembed
        xc = jax.lax.dynamic_slice_in_dim(
            x, num_decode + jnp.maximum(chunk_valid - 1, 0), 1, axis=0
        )
        x = jnp.concatenate([x[:num_decode], xc], axis=0)  # (S+1, 1, D)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[:, 0]
        return new_pages, logits

    # ------------------------------------------------------------------
    # chunked prefill (continuous batching)
    # ------------------------------------------------------------------
    def prefill_chunk(self, params, pages, block_table, tokens, start, valid):
        """One fixed-size prefill chunk of ONE sequence, scattered into its
        existing page set.

        pages: the shared page pool, as in ``decode_step_paged``.
        block_table (MP,) int32 is the sequence's row; tokens (C,) int32 is
        the chunk (C static — one compile covers every prompt); start
        (scalar int32) is how many positions are already resident (shared
        prefix pages + earlier chunks); valid (scalar int32) is the number
        of real tokens in this possibly-padded chunk.

        Returns (new_pages, logits (Vp,) f32) where logits belong to chunk
        position ``valid - 1`` — meaningful on the prompt's final chunk
        (the first sampling position), garbage (and ignored) before that.
        Token-embedding families only (dense/moe); vlm prompts carry vision
        embeds and keep the whole-prompt bucketed prefill path.

        The chunk attention lowers per ``self.attn_impl`` exactly like the
        paged decode step: the Pallas chunk-prefill kernel on TPU (sharded
        serving dispatches it per kv-head shard), the XLA oracle elsewhere
        — same contract either way, asserted by the differential fuzz sweep
        in ``tests/test_kernel_fuzz.py``.
        """
        cfg = self.cfg
        assert cfg.family in ("dense", "moe"), cfg.family
        x = jnp.take(params["embed"], tokens[None], axis=0)  # (1,C,D)

        def attend(p, h, pages, layer):
            return attn.prefill_chunk_attention_paged(
                p, h, pages, layer, block_table, start, valid, cfg,
                attn_impl=self.attn_impl,
            )

        x, new_pages = self._paged_layers(params, pages, x, attend)
        x = jax.lax.dynamic_slice_in_dim(x, valid - 1, 1, axis=1)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[0, 0]
        return new_pages, logits

    # ------------------------------------------------------------------
    # speculative verify (continuous batching)
    # ------------------------------------------------------------------
    def verify_step_paged(self, params, pages, block_table, tokens, start,
                          valid):
        """Score a speculation bundle: C chunk-style rows of ONE sequence —
        the last committed token followed by k drafted tokens — scattered
        and attended exactly like a prefill chunk, but unembedding ALL C
        rows instead of just the last.

        pages: the shared page pool, as in ``decode_step_paged``.
        block_table (MP,) int32 is the sequence's row; tokens (C,) int32 is
        ``[t_last, d_1 .. d_k]`` padded to the static bundle width; start
        (scalar int32) is the sequence's cached length L (t_last's KV lands
        at position L, draft i at L+i); valid (scalar int32) is ``1 + k``
        for this bundle (padded rows past it write out of bounds and return
        garbage the caller ignores).

        Returns (new_pages, logits (C, Vp) f32): row i is the distribution
        over the token at index ``idx0 + i`` given the committed history
        plus drafts ``d_1 .. d_i`` — exactly what a sequential i-step
        decode loop would produce, which is why acceptance under the
        ``(seed, token_index)``-keyed sampler reproduces the spec-off
        stream byte-for-byte. Attention rides the SAME chunk path as
        ``prefill_chunk`` (``ops.paged_prefill_attention`` — the mixed
        kernel's chunk half), so one fused dispatch both writes the k+1
        candidate KV positions and scores them; rejection is a pure
        host-side length rewind."""
        cfg = self.cfg
        assert cfg.family in ("dense", "moe"), cfg.family
        x = jnp.take(params["embed"], tokens[None], axis=0)  # (1,C,D)

        def attend(p, h, pages, layer):
            return attn.prefill_chunk_attention_paged(
                p, h, pages, layer, block_table, start, valid, cfg,
                attn_impl=self.attn_impl,
            )

        x, new_pages = self._paged_layers(params, pages, x, attend)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)  # (1,C,D)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[0]  # (C, Vp)
        return new_pages, logits

    # ------------------------------------------------------------------
    # recurrent-state serving (SSM / hybrid continuous batching)
    # ------------------------------------------------------------------
    def decode_step_ssm(self, params, state, tokens, active):
        """One token per in-flight slot against the per-slot state bank.

        state: the ``init_mamba_cache`` pytree stacked over layers and
        batched over slots — ssm (L,S,HN,PN,N) f32 plus conv tails. tokens
        (S, 1) int32 is each slot's last token; active (S,) int32 masks
        idle slots, whose state is left untouched (their rows still run —
        shapes stay static so the jitted step never recompiles — but the
        writeback is gated). Returns (new_state, logits (S, Vp) f32).
        """
        cfg = self.cfg
        assert cfg.family == "ssm", cfg.family
        x = jnp.take(params["embed"], tokens, axis=0)  # (S,1,D)

        def body(x, inp):
            pl, cl = inp
            h = rms_norm(x, pl["ln"], cfg.norm_eps)
            h, new_cl = ssm_mod.mamba_decode(
                pl["mamba"], h, cl, cfg, ssd_impl=self.ssd_impl
            )
            return x + h, new_cl

        x, new_state = jax.lax.scan(body, x, (params["layers"], dict(state)))
        new_state = self._mask_state(new_state, dict(state), active)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[:, 0]
        return new_state, logits

    def prefill_chunk_ssm(self, params, state_slot, tokens, valid):
        """One fixed-size prefill chunk of ONE sequence through the SSD
        scan, continuing from (and returning) the slot's carried state.

        state_slot: one slot's state with the slot axis kept singleton —
        ssm (L,1,HN,PN,N) f32 plus conv tails. tokens (C,) int32 (C
        static); valid (scalar int32) is the number of real tokens in this
        possibly-padded chunk (padded positions are exact identities on
        the recurrence — see ``mamba_prefill_chunk``). Returns
        (new_state_slot, logits (Vp,) f32) where logits belong to chunk
        position ``valid - 1`` — meaningful on the prompt's final chunk,
        garbage (and ignored) before that.
        """
        cfg = self.cfg
        assert cfg.family == "ssm", cfg.family
        x = jnp.take(params["embed"], tokens[None], axis=0)  # (1,C,D)

        def body(x, inp):
            pl, cl = inp
            h = rms_norm(x, pl["ln"], cfg.norm_eps)
            h, new_cl = ssm_mod.mamba_prefill_chunk(
                pl["mamba"], h, cl, cfg, valid=valid, ssd_impl=self.ssd_impl
            )
            return x + h, new_cl

        x, new_state = jax.lax.scan(body, x, (params["layers"], dict(state_slot)))
        x = jax.lax.dynamic_slice_in_dim(
            x, jnp.maximum(valid - 1, 0), 1, axis=1
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[0, 0]
        return new_state, logits

    def decode_step_hybrid(self, params, pages, state, block_tables,
                           lengths, tokens, active):
        """Hybrid (Zamba2) paged decode: the shared attention block reads
        and writes the g-layer paged KV pool (g = L // attn_every) while
        every Mamba layer steps the per-slot state bank — one fused pass.

        pages: the g-layer pool, laid out as in ``decode_step_paged``;
        state: the stacked mamba bank (slot axis second); block_tables (S,
        MP) / lengths (S,) index the attention pool exactly like
        ``decode_step_paged``.
        Returns (new_pages, new_state, logits (S, Vp) f32).
        """
        cfg = self.cfg
        assert cfg.family == "hybrid", cfg.family
        g = cfg.num_layers // cfg.attn_every
        x = jnp.take(params["embed"], tokens, axis=0)  # (S,1,D)
        grouped = jax.tree.map(
            lambda a: a.reshape((g, cfg.attn_every) + a.shape[1:]),
            params["layers"],
        )
        gstate = jax.tree.map(
            lambda a: a.reshape((g, cfg.attn_every) + a.shape[1:]), dict(state)
        )
        shared = params["shared"]

        def group_body(carry, inp):
            x, pages = carry
            pg, mcg, layer = inp
            h = rms_norm(x, shared["ln1"], cfg.norm_eps)
            h, pages = attn.decode_self_attention_paged(
                shared["attn"], h, pages, layer, block_tables, lengths, cfg,
                attn_impl=self.attn_impl,
            )
            x = x + h
            h = rms_norm(x, shared["ln2"], cfg.norm_eps)
            x = x + swiglu(h, shared["mlp"]["w_gate"], shared["mlp"]["w_up"],
                           shared["mlp"]["w_down"])

            def mbody(xc, inp2):
                pl, cl2 = inp2
                hh = rms_norm(xc, pl["ln"], cfg.norm_eps)
                hh, new_cl2 = ssm_mod.mamba_decode(
                    pl["mamba"], hh, cl2, cfg, ssd_impl=self.ssd_impl
                )
                return xc + hh, new_cl2

            x, new_mcs = jax.lax.scan(mbody, x, (pg, mcg))
            return (x, pages), new_mcs

        # the attention pool rides the carry and is written in place, as in
        # ``_paged_layers``
        (x, pages), ys = jax.lax.scan(
            group_body, (x, dict(pages)),
            (grouped, gstate, jnp.arange(g, dtype=jnp.int32)),
        )
        new_state = jax.tree.map(
            lambda a: a.reshape((cfg.num_layers,) + a.shape[2:]), ys
        )
        new_state = self._mask_state(new_state, dict(state), active)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[:, 0]
        return pages, new_state, logits

    def prefill_chunk_hybrid(self, params, pages, state_slot, block_table,
                             tokens, start, valid):
        """Hybrid chunked prefill of ONE sequence: attention chunk rows
        scatter into the sequence's pages (positions ``start..start+valid``)
        while the Mamba layers continue from the slot's carried state.
        Returns (new_pages, new_state_slot, logits (Vp,) f32) with logits
        at chunk position ``valid - 1`` as in ``prefill_chunk``.
        """
        cfg = self.cfg
        assert cfg.family == "hybrid", cfg.family
        g = cfg.num_layers // cfg.attn_every
        x = jnp.take(params["embed"], tokens[None], axis=0)  # (1,C,D)
        grouped = jax.tree.map(
            lambda a: a.reshape((g, cfg.attn_every) + a.shape[1:]),
            params["layers"],
        )
        gstate = jax.tree.map(
            lambda a: a.reshape((g, cfg.attn_every) + a.shape[1:]),
            dict(state_slot),
        )
        shared = params["shared"]

        def group_body(carry, inp):
            x, pages = carry
            pg, mcg, layer = inp
            h = rms_norm(x, shared["ln1"], cfg.norm_eps)
            h, pages = attn.prefill_chunk_attention_paged(
                shared["attn"], h, pages, layer, block_table, start, valid,
                cfg, attn_impl=self.attn_impl,
            )
            x = x + h
            h = rms_norm(x, shared["ln2"], cfg.norm_eps)
            x = x + swiglu(h, shared["mlp"]["w_gate"], shared["mlp"]["w_up"],
                           shared["mlp"]["w_down"])

            def mbody(xc, inp2):
                pl, cl2 = inp2
                hh = rms_norm(xc, pl["ln"], cfg.norm_eps)
                hh, new_cl2 = ssm_mod.mamba_prefill_chunk(
                    pl["mamba"], hh, cl2, cfg, valid=valid,
                    ssd_impl=self.ssd_impl,
                )
                return xc + hh, new_cl2

            x, new_mcs = jax.lax.scan(mbody, x, (pg, mcg))
            return (x, pages), new_mcs

        (x, pages), ys = jax.lax.scan(
            group_body, (x, dict(pages)),
            (grouped, gstate, jnp.arange(g, dtype=jnp.int32)),
        )
        new_state = jax.tree.map(
            lambda a: a.reshape((cfg.num_layers,) + a.shape[2:]), ys
        )
        x = jax.lax.dynamic_slice_in_dim(
            x, jnp.maximum(valid - 1, 0), 1, axis=1
        )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = all_gather_logits(jnp.einsum(
            "bsd,dv->bsv", x, self._unembed_weight(params),
            preferred_element_type=jnp.float32,
        ))[0, 0]
        return pages, new_state, logits

    @staticmethod
    def _mask_state(new_state, old_state, active):
        """Gate the state-bank writeback on per-slot activity (slot axis
        is second — leaves are stacked (L, S, ...))."""
        keep = active.astype(bool)

        def leaf(new, old):
            m = keep.reshape((1, -1) + (1,) * (new.ndim - 2))
            return jnp.where(m, new, old)

        return jax.tree.map(leaf, new_state, old_state)
