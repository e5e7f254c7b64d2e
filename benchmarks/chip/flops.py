"""Operations and bytes the algorithm needs, from shapes alone.

``dm`` is a reference's dims (``references/<name>.py::dims``). FLOPs count
a multiply-add as 2. Model FLOPs per row follow the published layer: the
matmuls, attention over the row's context (4 * heads * head_dim * context
per layer: scores and values), the SSD recurrence per token (6 * heads *
head_dim * state per layer: decay, input and readout), and the output
projection where the row produces logits. Kernel bytes are the least the
kernel has to move: each sequence's unpadded keys and values once, queries
and outputs once; for the SSD decode step the float32 state of the slots
that decode, read and written, and its per-token inputs and outputs.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def lm_layer_params(dm: dict) -> int:
    d, h, kvh, hd, f = (dm["d"], dm["heads"], dm["kv_heads"], dm["head_dim"],
                        dm["d_ff"])
    return d * (h + 2 * kvh) * hd + h * hd * d + 3 * d * f


def ssm_layer_params(dm: dict) -> int:
    d, din, n, h = dm["d"], dm["d_inner"], dm["state"], dm["heads"]
    return d * (2 * din + 2 * n + h) + din * d


def row_flops(dm: dict, ctx: int, logits: bool) -> float:
    """Model FLOPs of one token row (one sequence position) attending
    ``ctx`` positions (ignored for attention-free models)."""
    if "kv_heads" in dm:
        per_layer = 2 * lm_layer_params(dm) + 4 * dm["heads"] * dm["head_dim"] * ctx
    else:
        per_layer = (2 * ssm_layer_params(dm)
                     + 6 * dm["heads"] * dm["head_dim"] * dm["state"]
                     + 2 * dm["conv"] * (dm["d_inner"] + 2 * dm["state"]))
    out = dm["layers"] * per_layer
    if logits:
        out += 2 * dm["d"] * dm["vocab"]
    return float(out)


def step_flops(dm: dict, rec: dict) -> float:
    """Model FLOPs of one executor call as ``engines/*.watch`` logged it:
    decode rows (each producing logits) and a prefill chunk (its live
    rows; logits for its last row only). Padded rows count nothing."""
    tot = 0.0
    for c in rec.get("decode_ctx", ()):
        tot += row_flops(dm, c, logits=True)
    tot += rec.get("decode_active", 0) * row_flops(dm, 0, logits=True)
    if rec.get("chunk"):
        start, valid = rec["chunk"]
        for i in range(valid):
            tot += row_flops(dm, start + i + 1, logits=False)
        tot += 2 * dm["d"] * dm["vocab"]
    return tot


def paged_attention_call(dm: dict, rec: dict) -> tuple[float, float]:
    """(FLOPs, least bytes) of ONE layer's paged attention kernel call in
    the logged executor call: decode rows and chunk rows together."""
    h, kvh, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    kv_per_pos = 2 * kvh * hd * BF16
    flops = bytes_ = 0.0
    rows = 0
    for c in rec.get("decode_ctx", ()):
        flops += 4 * h * hd * c
        bytes_ += c * kv_per_pos
        rows += 1
    if rec.get("chunk"):
        start, valid = rec["chunk"]
        flops += sum(4 * h * hd * (start + i + 1) for i in range(valid))
        bytes_ += (start + valid) * kv_per_pos
        rows += valid
    bytes_ += rows * 2 * h * hd * BF16  # queries in, outputs out
    return flops, bytes_


def ssd_decode_call(dm: dict, rec: dict) -> tuple[float, float]:
    """(FLOPs, least bytes) of ONE layer's SSD decode kernel call: the
    state of each decoding slot read and written in float32, plus its
    x, dt, B, C in and y out."""
    n_act = rec.get("decode_active", 0)
    h, p, n = dm["heads"], dm["head_dim"], dm["state"]
    flops = n_act * 6 * h * p * n
    state = 2 * h * p * n * F32
    io = (2 * h * p + h + 2 * n) * F32
    return float(flops), float(n_act * (state + io))
