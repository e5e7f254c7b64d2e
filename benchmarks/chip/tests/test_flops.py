"""flops.py against counts made by hand at the published widths."""

import json
from pathlib import Path

import pytest

import flops
from references import llama, mamba2

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def dims(name, ref):
    return ref.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_smollm_dims_and_layer_params():
    dm = dims("smollm-360m", llama)
    assert (dm["layers"], dm["d"], dm["heads"], dm["kv_heads"], dm["head_dim"],
            dm["d_ff"], dm["vocab"]) == (32, 960, 15, 5, 64, 2560, 49152)
    # q 960x960, k and v 960x320 each, o 960x960, MLP 3 x 960x2560
    assert flops.lm_layer_params(dm) == (960 * 960 + 2 * 960 * 320
                                         + 960 * 960 + 3 * 960 * 2560)
    assert flops.lm_layer_params(dm) == 9_830_400
    # with the norm scales and the tied embedding: the published 361.82M
    norms = 32 * 2 * 960 + 960
    assert 32 * flops.lm_layer_params(dm) + norms + 49152 * 960 == 361_821_120


def test_smollm_row_flops():
    dm = dims("smollm-360m", llama)
    layer = 2 * 9_830_400 + 4 * 15 * 64 * 100  # matmuls + attention at 100
    assert flops.row_flops(dm, 100, logits=False) == 32 * layer
    assert (flops.row_flops(dm, 100, logits=True)
            == 32 * layer + 2 * 960 * 49152)


def test_paged_attention_call():
    dm = dims("smollm-360m", llama)
    rec = {"decode_ctx": [10, 20], "chunk": (100, 4)}
    f, b = flops.paged_attention_call(dm, rec)
    h, hd = 15, 64
    ctx = 10 + 20 + (101 + 102 + 103 + 104)
    assert f == 4 * h * hd * ctx
    # K and V of 5 heads x 64 in bf16 per position: 1280 B; the chunk's
    # sequence once (104 positions), queries and outputs of 6 rows
    assert b == (10 + 20 + 104) * 1280 + 6 * 2 * h * hd * 2


def test_mamba2_dims_and_decode_call():
    dm = dims("mamba2-1.3b", mamba2)
    assert (dm["layers"], dm["d"], dm["d_inner"], dm["heads"], dm["head_dim"],
            dm["state"], dm["vocab"], dm["tied"]) == (
                48, 2048, 4096, 64, 64, 128, 50288, True)
    # in_proj z, x (2 x 2048x4096), B, C (2 x 2048x128), dt (2048x64); out
    assert flops.ssm_layer_params(dm) == (2048 * (2 * 4096 + 2 * 128 + 64)
                                          + 4096 * 2048)
    f, b = flops.ssd_decode_call(dm, {"decode_active": 3})
    assert f == 3 * 6 * 64 * 64 * 128
    # one slot's state of one layer: 64 x 64 x 128 float32 = 2 MiB, read
    # and written, plus x, y (64 x 64), dt (64), B, C (128) in float32
    per_slot = 2 * 64 * 64 * 128 * 4 + (2 * 64 * 64 + 64 + 2 * 128) * 4
    assert b == 3 * per_slot
    # the whole bank's state per slot: 48 layers -> 100.7 MB
    assert 48 * 64 * 64 * 128 * 4 == pytest.approx(100.7e6, rel=1e-3)


def test_step_flops_counts_live_rows_only():
    dm = dims("smollm-360m", llama)
    rec = {"decode_ctx": [5], "chunk": (0, 3)}
    want = (flops.row_flops(dm, 5, True)
            + sum(flops.row_flops(dm, i + 1, False) for i in range(3))
            + 2 * 960 * 49152)
    assert flops.step_flops(dm, rec) == want
