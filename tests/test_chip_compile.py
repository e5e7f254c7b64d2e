"""Compile every Pallas kernel of the serving path for a described TPU v5e.

Nothing runs: the TPU compiler that ships with ``libtpu`` compiles each
kernel for a ``v5e:2x2`` topology that is described, not attached, and
raises what the chip's compiler would raise (block shapes that break the
(8, 128) tiling rule, VMEM over-use, unsupported lowerings). Interpret-mode
tests cannot see any of that.

Each kernel's custom call must carry the kernel's name in the compiled
HLO: that instruction name is what a profiler trace of the chip prints.

Widths are the published ones: smollm-360m (KVH 5, G 3, D 64), the
per-shard slice of llama3-8b at tp=4 (KVH 2, G 4, D 128) and mamba2-1.3b
(64 SSD heads, P 64, N 128).

The paged model steps are compiled whole too, at those widths over the
smollm-360m benchmark cell's pool of 6145 pages, as a guard on the pool's
layout: the step may move no pool-sized data. The only op whose shape
holds the pool's page count is the in-place row write; the donated pool
comes back aliased; and the compiler's scratch stays below one layer's
share of the pool.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library at a time, and under pytest-xdist
every worker imports this file. Keep these tests in this one file so one
worker owns the library.
"""

from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import (
    paged_attention_bkgd,
    paged_mixed_attention_rkgd,
    paged_prefill_attention_ckgd,
)
from repro.kernels.ssd_scan import ssd_decode_step_bh, ssd_scan_bhsp
from repro.models import build_model

# (label, kv heads, group, head dim)
ATTN_WIDTHS = [
    ("smollm-360m", 5, 3, 64),
    ("llama3-8b-tp4-shard", 2, 4, 128),
]
PAGE, NUM_PAGES, MAX_PAGES, LAYERS = 16, 64, 16, 3
SLOTS, CHUNK = 8, 64
# mamba2-1.3b: d_inner 4096 / head dim 64 = 64 heads, state 128
SSD_HEADS, SSD_P, SSD_N = 64, 64, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(sharding, name, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's custom call is named after it: "%<name>[.n] = ... custom-call("
    assert re.search(rf"%{name}(\.\d+)? = [^\n]*custom-call\(", text), name
    return compiled


def _pool(kvh, d, dtype=jnp.bfloat16):
    """The stacked lane-dense pool (L, P, page, KVH*2*D)."""
    return ((LAYERS, NUM_PAGES, PAGE, kvh * 2 * d), dtype)


LAYER = ((), jnp.int32)


@pytest.mark.parametrize("width", ATTN_WIDTHS, ids=lambda w: w[0])
def test_paged_decode_compiles(one_chip, width):
    _, kvh, g, d = width
    _compile(one_chip, "paged_decode_attention", paged_attention_bkgd,
             ((SLOTS, kvh, g, d), jnp.bfloat16), _pool(kvh, d),
             ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32), LAYER)


@pytest.mark.parametrize("width", ATTN_WIDTHS, ids=lambda w: w[0])
def test_paged_prefill_compiles(one_chip, width):
    _, kvh, g, d = width
    _compile(one_chip, "paged_prefill_attention",
             paged_prefill_attention_ckgd,
             ((CHUNK, kvh, g, d), jnp.bfloat16), _pool(kvh, d),
             ((MAX_PAGES,), jnp.int32), ((), jnp.int32), ((), jnp.int32),
             LAYER)


@pytest.mark.parametrize("width", ATTN_WIDTHS, ids=lambda w: w[0])
def test_paged_mixed_compiles(one_chip, width):
    _, kvh, g, d = width
    rows = SLOTS + CHUNK
    _compile(one_chip, "paged_mixed_attention", paged_mixed_attention_rkgd,
             ((rows, kvh, g, d), jnp.bfloat16), _pool(kvh, d),
             ((rows, MAX_PAGES), jnp.int32), ((rows,), jnp.int32), LAYER)


def test_paged_mixed_int8_compiles(one_chip):
    _, kvh, g, d = ATTN_WIDTHS[0]
    rows = SLOTS + CHUNK
    fn = lambda q, kv, s, bt, lp, layer: paged_mixed_attention_rkgd(
        q, kv, bt, lp, layer, kv_scale=s)
    _compile(one_chip, "paged_mixed_attention", fn,
             ((rows, kvh, g, d), jnp.bfloat16), _pool(kvh, d, jnp.int8),
             ((LAYERS, NUM_PAGES, PAGE, kvh * 2), jnp.float32),
             ((rows, MAX_PAGES), jnp.int32), ((rows,), jnp.int32), LAYER)


# ---------------------------------------------------------------------------
# whole paged steps: the pool is written in place and never copied
# ---------------------------------------------------------------------------

# the smollm-360m cells' pool (48 slots x 128 pages of 16, + the null page),
# 2 layers: what one layer does, the loop does for each
CELL_PAGES, CELL_SLOTS, STEP_LAYERS = 6145, 48, 2
# ops that are views or bookkeeping, whatever their shape
_FREE_OPS = {"parameter", "get-tuple-element", "bitcast", "custom-call"}
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*)$")


def _step_model(width):
    """A 2-layer model at a width of ``ATTN_WIDTHS``: smollm-360m itself,
    or one tp=4 shard of llama3-8b (its q/kv heads, ff and vocab over 4)."""
    if width == "smollm-360m":
        cfg = ARCHS["smollm-360m"]
    else:
        base = ARCHS["llama3-8b"]
        cfg = dataclasses.replace(
            base, num_heads=base.num_heads // 4,
            num_kv_heads=base.num_kv_heads // 4, d_ff=base.d_ff // 4,
            vocab_size=base.vocab_size // 4)
    cfg = dataclasses.replace(cfg, num_layers=STEP_LAYERS, dtype="bfloat16")
    return cfg, build_model(cfg, attn_impl="pallas")


def _step_args(step, cfg, model, sharding):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    i32 = lambda *shape: sds(shape, jnp.int32)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), model.abstract())
    pool = {"kv": sds((STEP_LAYERS, CELL_PAGES, PAGE,
                       cfg.eff_kv_heads * 2 * cfg.head_dim), jnp.bfloat16)}
    mp, s, c = 128, CELL_SLOTS, CHUNK
    if step == "decode":
        return (model.decode_step_paged,
                (params, pool, i32(s, mp), i32(s), i32(s, 1)))
    if step == "mixed":
        fn = lambda p, pg, bt, pos, tok, cv: model.mixed_step_paged(
            p, pg, bt, pos, tok, num_decode=s, chunk_valid=cv)
        return fn, (params, pool, i32(s + c, mp), i32(s + c),
                    i32(s + c, 1), i32())
    return (model.prefill_chunk,
            (params, pool, i32(mp), i32(c), i32(), i32()))


def _pool_moves(text: str) -> list[str]:
    """Instructions whose shape holds the pool's page count and that move
    data, other than the row write: a scatter, or a fusion whose root is
    one (the loop's in-place update of its carried pool)."""
    roots, comp = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
        m = _INSTR.match(line)
        if m and line.lstrip().startswith("ROOT"):
            roots[comp] = m.group(3)
    bad = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or str(CELL_PAGES) not in m.group(2).split(","):
            continue
        op = m.group(3)
        if op in _FREE_OPS or op == "scatter":
            continue
        called = re.search(r"calls=%([^,\s]+)", line)
        if op == "fusion" and called and roots.get(called.group(1)) == "scatter":
            continue
        bad.append(line.strip()[:200])
    return bad


@pytest.mark.parametrize("step", ["decode", "mixed", "prefill"])
@pytest.mark.parametrize("width", [w[0] for w in ATTN_WIDTHS])
def test_paged_step_moves_no_pool(one_chip, monkeypatch, width, step):
    # the described chip has no backend of its own here: steer the model's
    # "pallas" ops to the compiled kernels, as on the chip
    monkeypatch.setattr(ops, "_resolve_pallas_impl",
                        lambda impl, interpret, op: ("pallas", False))
    cfg, model = _step_model(width)
    fn, args = _step_args(step, cfg, model, one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text = compiled.as_text()
    assert re.search(r"%paged_\w+_attention(\.\d+)? = ", text)
    assert _pool_moves(text) == []
    pool = args[1]["kv"]
    pool_bytes = pool.size * pool.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes  # donated, aliased
    assert mem.temp_size_in_bytes < pool_bytes // STEP_LAYERS


@pytest.mark.parametrize("seq", [CHUNK, 512], ids=["chunk64", "seq512"])
def test_ssd_scan_compiles(one_chip, seq):
    fn = lambda x, dt, a, b, c: ssd_scan_bhsp(x, dt, a, b, c, chunk=256)
    _compile(one_chip, "ssd_chunk_scan", fn,
             ((1, SSD_HEADS, seq, SSD_P), jnp.bfloat16),
             ((1, SSD_HEADS, seq), jnp.float32), ((SSD_HEADS,), jnp.float32),
             ((1, seq, SSD_N), jnp.bfloat16), ((1, seq, SSD_N), jnp.bfloat16))


def test_ssd_decode_step_compiles(one_chip):
    _compile(one_chip, "ssd_decode_step", ssd_decode_step_bh,
             ((SLOTS, SSD_HEADS, SSD_P, SSD_N), jnp.float32),
             ((SLOTS, SSD_HEADS, SSD_P), jnp.bfloat16),
             ((SLOTS, SSD_HEADS), jnp.float32), ((SSD_HEADS,), jnp.float32),
             ((SLOTS, SSD_N), jnp.bfloat16), ((SLOTS, SSD_N), jnp.bfloat16))


@pytest.mark.parametrize("width", ATTN_WIDTHS, ids=lambda w: w[0])
def test_flash_attention_compiles(one_chip, width):
    _, kvh, g, d = width
    _compile(one_chip, "flash_attention", flash_attention_bhsd,
             ((1, kvh * g, 256, d), jnp.bfloat16),
             ((1, kvh, 256, d), jnp.bfloat16), ((1, kvh, 256, d), jnp.bfloat16))
