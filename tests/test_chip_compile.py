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

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library at a time, and under pytest-xdist
every worker imports this file. Keep these tests in this one file so one
worker owns the library.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.paged_attention import (
    paged_attention_bkgd,
    paged_mixed_attention_rkgd,
    paged_prefill_attention_ckgd,
)
from repro.kernels.ssd_scan import ssd_decode_step_bh, ssd_scan_bhsp

# (label, kv heads, group, head dim)
ATTN_WIDTHS = [
    ("smollm-360m", 5, 3, 64),
    ("llama3-8b-tp4-shard", 2, 4, 128),
]
PAGE, NUM_PAGES, MAX_PAGES = 16, 64, 16
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
    return ((NUM_PAGES, PAGE, kvh, d), dtype)


@pytest.mark.parametrize("width", ATTN_WIDTHS, ids=lambda w: w[0])
def test_paged_decode_compiles(one_chip, width):
    _, kvh, g, d = width
    _compile(one_chip, "paged_decode_attention", paged_attention_bkgd,
             ((SLOTS, kvh, g, d), jnp.bfloat16),
             _pool(kvh, d), _pool(kvh, d),
             ((SLOTS, MAX_PAGES), jnp.int32), ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("width", ATTN_WIDTHS, ids=lambda w: w[0])
def test_paged_prefill_compiles(one_chip, width):
    _, kvh, g, d = width
    _compile(one_chip, "paged_prefill_attention",
             paged_prefill_attention_ckgd,
             ((CHUNK, kvh, g, d), jnp.bfloat16),
             _pool(kvh, d), _pool(kvh, d),
             ((MAX_PAGES,), jnp.int32), ((), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("width", ATTN_WIDTHS, ids=lambda w: w[0])
def test_paged_mixed_compiles(one_chip, width):
    _, kvh, g, d = width
    rows = SLOTS + CHUNK
    _compile(one_chip, "paged_mixed_attention", paged_mixed_attention_rkgd,
             ((rows, kvh, g, d), jnp.bfloat16),
             _pool(kvh, d), _pool(kvh, d),
             ((rows, MAX_PAGES), jnp.int32), ((rows,), jnp.int32))


def test_paged_mixed_int8_compiles(one_chip):
    _, kvh, g, d = ATTN_WIDTHS[0]
    rows = SLOTS + CHUNK
    fn = lambda q, k, v, ks, vs, bt, lp: paged_mixed_attention_rkgd(
        q, k, v, bt, lp, k_scale=ks, v_scale=vs)
    scales = ((NUM_PAGES, PAGE, kvh), jnp.float32)
    _compile(one_chip, "paged_mixed_attention", fn,
             ((rows, kvh, g, d), jnp.bfloat16),
             _pool(kvh, d, jnp.int8), _pool(kvh, d, jnp.int8),
             scales, scales,
             ((rows, MAX_PAGES), jnp.int32), ((rows,), jnp.int32))


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
