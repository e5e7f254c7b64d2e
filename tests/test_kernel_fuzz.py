"""Differential kernel fuzzing: every Pallas kernel vs its ``kernels/ref.py``
oracle, in interpret mode, over seeded randomized parameter sweeps.

The hand-picked shapes in ``test_kernels.py`` / ``test_serving_paged.py``
pin known-tricky cases; this harness systematically sweeps the shape space
the serving engine actually visits — chunk sizes 1/odd/page-straddling,
history lengths 0..multi-page, partial last pages, COW-forked block tables,
GQA/MQA groupings — and asserts kernel-vs-oracle parity ≤ 1e-3 (the repo
contract from ``ops.py``), reporting the exact failing parameter tuple on
mismatch so a regression reproduces with one ``pytest -k`` invocation.

Sweeps are a deterministic seeded grid plus a hypothesis property pass.
CI runs this file in the dedicated interpret-mode kernel
job next to ``test_kernels.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.serving.kv_cache import NULL_PAGE, cdiv

TOL = 1e-3  # max abs error bound, kernel vs oracle (f32; observed ~1e-6)


LAYER = 1  # every pool below stacks 3 layers; the calls read the middle one


def _stack(kp, vp):
    """Logical (P, page, KVH, D) K and V pages -> the serving pool's
    stacked lane-dense layout (``ref.pack_kv``), the case's pages at layer
    ``LAYER`` between two layers of other values, so a kernel that reads
    the wrong layer or mixes up K and V lanes fails the comparison."""
    kv = ref.pack_kv(jnp.asarray(kp), jnp.asarray(vp))
    other = jnp.full_like(kv, 3)
    return jnp.stack([other, kv, -other])


def _stack_q(kq, ks, vq, vs):
    """The int8 counterpart of :func:`_stack`: (pool, scales)."""
    sc = ref.pack_kv_scales(ks, vs)
    return _stack(kq, vq), jnp.stack(
        [jnp.full_like(sc, 0.5), sc, jnp.full_like(sc, 2.0)])


def _assert_close(got, want, params, kind):
    err = float(jnp.abs(jnp.asarray(got, jnp.float32)
                        - jnp.asarray(want, jnp.float32)).max())
    assert err <= TOL, f"{kind}: err={err:.3e} > {TOL} at shape tuple {params}"


# ---------------------------------------------------------------------------
# chunked-prefill paged attention
# ---------------------------------------------------------------------------
# params: (c, start, valid, h, kvh, d, page, extra_mp)
#   c      chunk width (static padded size)
#   start  history positions already cached (0 = fresh prompt)
#   valid  real tokens in the chunk (< c = padded chunk)
#   extra_mp  trailing null-page block-table entries past the live pages

_PREFILL_EDGES = [
    (1, 0, 1, 4, 2, 16, 8, 1),    # single query, no history (first token)
    (1, 17, 1, 4, 1, 8, 8, 0),    # C=1 deep in history: decode degenerate, MQA
    (3, 5, 3, 4, 4, 16, 8, 2),    # odd chunk, history mid-page, MHA
    (8, 0, 8, 4, 2, 16, 8, 0),    # chunk == page, aligned
    (8, 3, 8, 4, 2, 16, 8, 1),    # chunk straddles a page boundary
    (8, 29, 5, 8, 2, 16, 8, 1),   # multi-page history ending mid-page + pad
    (16, 8, 16, 8, 4, 32, 8, 0),  # chunk spans two whole pages
    (16, 15, 1, 4, 2, 16, 16, 1), # one live token landing last-in-page
    (5, 0, 0, 4, 2, 16, 8, 1),    # fully padded chunk -> exact zeros
    (32, 40, 32, 4, 2, 16, 16, 0),# big chunk over 2.5 pages of history
]


def _prefill_sweep():
    cases = list(_PREFILL_EDGES)
    rng = np.random.default_rng(0xC0FFEE)
    for _ in range(24):
        page = int(rng.choice([4, 8, 16]))
        c = int(rng.integers(1, 33))
        start = int(rng.integers(0, 4 * page))
        valid = int(rng.integers(1, c + 1))
        group = int(rng.choice([1, 2, 4]))
        kvh = int(rng.choice([1, 2, 4]))
        d = int(rng.choice([8, 16, 32]))
        cases.append((c, start, valid, kvh * group, kvh, d, page,
                      int(rng.integers(0, 3))))
    return cases


def _prefill_case(params, seed, forked=False):
    """Pool + block table for one chunked-prefill call.

    ``forked``: the table's live pages alias a twin sequence's pages (the
    COW/fork layout — sharing is invisible to the kernel, but the aliased
    ids exercise non-contiguous, non-monotonic physical page order).
    """
    c, start, valid, h, kvh, d, page, extra = params
    rng = np.random.default_rng(seed)
    total = start + valid
    need = cdiv(max(total, 1), page)
    num_pages = need * (2 if forked else 1) + 3
    q = jnp.asarray(rng.standard_normal((c, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    perm = rng.permutation(np.arange(1, num_pages))
    bt = np.full((need + extra,), NULL_PAGE, np.int32)
    bt[:need] = perm[:need]
    if forked and need > 1:
        # fork: shared prefix pages + a privately COW-copied tail page
        bt[need - 1] = perm[need]
    return q, kp, vp, jnp.asarray(bt), jnp.int32(start), jnp.int32(valid)


@pytest.mark.parametrize("params", _prefill_sweep(),
                         ids=lambda p: "c{}s{}v{}h{}k{}d{}p{}x{}".format(*p))
def test_paged_prefill_kernel_vs_oracle(params):
    for seed, forked in ((0, False), (1, True)):
        q, kp, vp, bt, start, valid = _prefill_case(params, seed, forked)
        want = ref.paged_prefill_attention_ref(q, kp, vp, bt, start, valid)
        got = ops.paged_prefill_attention(
            q, _stack(kp, vp), bt, start, valid, LAYER,
            impl="pallas_interpret",
        )
        _assert_close(got, want, params + (("forked",) if forked else ()),
                      "paged_prefill")


def test_paged_prefill_ref_vs_dense():
    """Semantic anchor: the oracle itself equals dense causal attention over
    the gathered sequence (queries are its last ``valid`` positions)."""
    for params in _PREFILL_EDGES:
        c, start, valid, h, kvh, d, page, _ = params
        if valid == 0:
            continue
        q, kp, vp, bt, s_, v_ = _prefill_case(params, seed=2)
        total = start + valid
        kd = np.stack([np.asarray(kp)[bt[j // page], j % page]
                       for j in range(total)])
        vd = np.stack([np.asarray(vp)[bt[j // page], j % page]
                       for j in range(total)])
        want = ref.flash_attention_ref(
            q[None, :valid], jnp.asarray(kd)[None], jnp.asarray(vd)[None],
            causal=True,
        )[0]
        got = ref.paged_prefill_attention_ref(q, kp, vp, bt, s_, v_)[:valid]
        _assert_close(got, want, params, "paged_prefill_ref_vs_dense")


def test_paged_prefill_chunk_walk_matches_dense():
    """Walk a whole prompt through the kernel chunk by chunk — scatter each
    chunk's K/V into the pages then attend — and require the concatenated
    outputs to equal ONE dense causal attention over the full prompt. This
    is the end-to-end contract ``DecoderLM.prefill_chunk`` relies on."""
    for plen, chunk, page, h, kvh, d in [
        (37, 8, 8, 4, 2, 16),   # partial last page AND partial last chunk
        (24, 5, 8, 4, 4, 8),    # odd chunk size straddling pages
        (16, 16, 4, 2, 1, 16),  # one chunk spanning 4 pages, MQA
    ]:
        rng = np.random.default_rng(plen)
        kd = rng.standard_normal((plen, kvh, d)).astype(np.float32)
        vd = rng.standard_normal((plen, kvh, d)).astype(np.float32)
        qd = rng.standard_normal((plen, h, d)).astype(np.float32)
        need = cdiv(plen, page)
        num_pages = need + 2
        kp = np.zeros((num_pages, page, kvh, d), np.float32)
        vp = np.zeros((num_pages, page, kvh, d), np.float32)
        bt = np.asarray(rng.permutation(np.arange(1, num_pages))[:need],
                        np.int32)
        outs = []
        for start in range(0, plen, chunk):
            valid = min(chunk, plen - start)
            for i in range(start, start + valid):  # the model's page scatter
                kp[bt[i // page], i % page] = kd[i]
                vp[bt[i // page], i % page] = vd[i]
            qc = np.zeros((chunk, h, d), np.float32)
            qc[:valid] = qd[start:start + valid]
            out = ops.paged_prefill_attention(
                jnp.asarray(qc), _stack(kp, vp),
                jnp.asarray(bt), jnp.int32(start), jnp.int32(valid), LAYER,
                impl="pallas_interpret",
            )
            outs.append(np.asarray(out)[:valid])
        want = ref.flash_attention_ref(
            jnp.asarray(qd)[None], jnp.asarray(kd)[None], jnp.asarray(vd)[None],
            causal=True,
        )[0]
        _assert_close(np.concatenate(outs), want,
                      (plen, chunk, page, h, kvh, d), "chunk_walk")


@settings(max_examples=15, deadline=None)
@given(
    c=st.integers(1, 24),
    start=st.integers(0, 40),
    pad=st.integers(0, 8),
    group=st.sampled_from([1, 2, 4]),
    kvh=st.sampled_from([1, 2]),
    page=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 2**16),
)
def test_paged_prefill_property(c, start, pad, group, kvh, page, seed):
    valid = max(1, c - pad)
    params = (c, start, valid, kvh * group, kvh, 8, page, 1)
    q, kp, vp, bt, s_, v_ = _prefill_case(params, seed)
    want = ref.paged_prefill_attention_ref(q, kp, vp, bt, s_, v_)
    got = ops.paged_prefill_attention(q, _stack(kp, vp), bt, s_, v_, LAYER,
                                      impl="pallas_interpret")
    _assert_close(got, want, params + (seed,), "paged_prefill_property")
    # convexity: live rows are convex combinations of V rows
    out = np.asarray(got)[:valid]
    assert np.isfinite(out).all()
    assert out.max() <= float(jnp.max(vp)) + 1e-4
    assert out.min() >= float(jnp.min(vp)) - 1e-4


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------
# params: (b, h, kvh, d, page, mp, alias)
#   alias: rows share physical pages (post-fork COW table layout)

def _decode_sweep():
    cases = [
        (1, 4, 2, 16, 8, 1, False),    # one seq, one page
        (3, 4, 2, 16, 8, 4, False),    # the classic mixed batch (idle row 0)
        (4, 8, 1, 8, 16, 2, False),    # MQA
        (4, 4, 4, 32, 4, 6, True),     # MHA, forked tables
        (6, 4, 2, 16, 8, 3, True),
    ]
    rng = np.random.default_rng(0xDEC0DE)
    for _ in range(16):
        kvh = int(rng.choice([1, 2, 4]))
        cases.append((
            int(rng.integers(1, 7)), kvh * int(rng.choice([1, 2, 4])), kvh,
            int(rng.choice([8, 16, 32])), int(rng.choice([4, 8, 16])),
            int(rng.integers(1, 5)), bool(rng.integers(0, 2)),
        ))
    return cases


def _decode_case(params, seed):
    b, h, kvh, d, page, mp, alias = params
    rng = np.random.default_rng(seed)
    num_pages = b * mp + 2
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    # lengths: always include an idle slot (0), a partial page and a full table
    lens = rng.integers(1, mp * page + 1, b).astype(np.int32)
    if b > 1:
        lens[0] = 0
    if b > 2:
        lens[1] = mp * page  # every page full
    bt = np.full((b, mp), NULL_PAGE, np.int32)
    nxt = 1
    for i in range(b):
        for p in range(cdiv(int(lens[i]), page)):
            if alias and i > 1 and p < cdiv(int(lens[1]), page) - 1:
                bt[i, p] = bt[1, p]  # shared prefix pages with row 1
            else:
                bt[i, p] = nxt
                nxt += 1
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens)


@pytest.mark.parametrize("params", _decode_sweep(),
                         ids=lambda p: "b{}h{}k{}d{}p{}m{}{}".format(
                             *p[:6], "a" if p[6] else ""))
def test_paged_decode_kernel_vs_oracle(params):
    for seed in (0, 1):
        q, kp, vp, bt, lens = _decode_case(params, seed)
        pool = _stack(kp, vp)
        want = ops.paged_attention(q, pool, bt, lens, LAYER,
                                   impl="xla_chunked")
        got = ops.paged_attention(q, pool, bt, lens, LAYER,
                                  impl="pallas_interpret")
        _assert_close(got, want, params + (seed,), "paged_decode")
        if int(lens[0]) == 0:
            assert (np.asarray(got)[0] == 0).all(), (
                f"idle slot must be exact zeros at {params}")


def test_paged_decode_equals_prefill_c1():
    """Cross-kernel consistency: decode is the C=1 chunk case."""
    params = (3, 4, 2, 16, 8, 3, False)
    q, kp, vp, bt, lens = _decode_case(params, seed=5)
    pool = _stack(kp, vp)
    dec = ops.paged_attention(q, pool, bt, lens, LAYER,
                              impl="pallas_interpret")
    for i in range(q.shape[0]):
        n = int(lens[i])
        if n == 0:
            continue
        chunk = ops.paged_prefill_attention(
            q[i][None], pool, bt[i], jnp.int32(n - 1), jnp.int32(1), LAYER,
            impl="pallas_interpret",
        )[0]
        _assert_close(chunk, dec[i], params + (i,), "decode_vs_prefill_c1")


# ---------------------------------------------------------------------------
# mixed prefill+decode paged attention (the fused engine step's kernel)
# ---------------------------------------------------------------------------
# params: (r, h, kvh, d, page, mp, n_dead, chunk_rows)
#   every row is ONE query position with its own (block_table, last_pos);
#   n_dead rows get last_pos=-1 (padding/idle — exact-zero output), the
#   last chunk_rows rows share one block table with consecutive last_pos
#   (a prefill chunk laid out as independent rows)


def _mixed_sweep():
    cases = [
        (1, 4, 2, 16, 8, 1, 0, 0),     # lone decode row
        (3, 4, 2, 16, 8, 4, 1, 0),     # decode batch with a dead row
        (4, 8, 1, 8, 16, 2, 0, 4),     # pure chunk, MQA
        (6, 4, 4, 32, 4, 2, 1, 3),     # the fused mix: decode+dead+chunk
        (5, 4, 2, 16, 8, 3, 4, 0),     # almost everything dead
    ]
    rng = np.random.default_rng(0x313DED)
    for _ in range(16):
        kvh = int(rng.choice([1, 2, 4]))
        r = int(rng.integers(1, 9))
        page = int(rng.choice([4, 8, 16]))
        mp = int(rng.integers(1, 5))
        ck = min(int(rng.integers(0, r + 1)), mp * page)
        cases.append((
            r, kvh * int(rng.choice([1, 2, 4])), kvh,
            int(rng.choice([8, 16, 32])), page, mp,
            int(rng.integers(0, r - ck + 1)), ck,
        ))
    return cases


def _mixed_case(params, seed):
    r, h, kvh, d, page, mp, n_dead, ck = params
    rng = np.random.default_rng(seed)
    num_pages = r * mp + 2
    q = jnp.asarray(rng.standard_normal((r, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((num_pages, page, kvh, d)), jnp.float32)
    last = rng.integers(0, mp * page, r).astype(np.int32)
    bt = np.full((r, mp), NULL_PAGE, np.int32)
    nxt = 1
    for i in range(r - ck):
        for p in range(cdiv(int(last[i]) + 1, page)):
            bt[i, p] = nxt
            nxt += 1
    if ck:
        # chunk rows: one shared table, consecutive positions ending mid-page
        # (start clamped so the run fits the mp-page table)
        start = int(rng.integers(0, max(mp * page - ck, 1)))
        last[r - ck:] = start + np.arange(ck)
        pages = cdiv(start + ck, page)
        bt[r - ck:, :pages] = np.arange(nxt, nxt + pages)
    order = rng.permutation(r - ck)  # dead rows anywhere among the decodes
    last[order[:n_dead]] = -1
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(last)


@pytest.mark.parametrize("params", _mixed_sweep(),
                         ids=lambda p: "r{}h{}k{}d{}p{}m{}x{}c{}".format(*p))
def test_paged_mixed_kernel_vs_oracle(params):
    for seed in (0, 1):
        q, kp, vp, bt, last = _mixed_case(params, seed)
        pool = _stack(kp, vp)
        want = ops.paged_mixed_attention(q, pool, bt, last, LAYER,
                                         impl="xla_chunked")
        got = ops.paged_mixed_attention(q, pool, bt, last, LAYER,
                                        impl="pallas_interpret")
        _assert_close(got, want, params + (seed,), "paged_mixed")
        dead = np.asarray(last) < 0
        assert (np.asarray(got)[dead] == 0).all(), (
            f"dead rows must be exact zeros at {params}")


def test_paged_mixed_subsumes_decode_and_chunk():
    """Cross-kernel consistency: with last_pos = lengths - 1 the mixed
    kernel IS paged decode, and a run of consecutive last_pos over a shared
    table IS the chunk-prefill kernel — the two dispatches the fused engine
    step replaces."""
    params = (4, 4, 2, 16, 8, 3, 1, 0)
    q, kp, vp, bt, last = _mixed_case(params, seed=3)
    lens = jnp.asarray(np.maximum(np.asarray(last) + 1, 0))
    pool = _stack(kp, vp)
    dec = ops.paged_attention(q, pool, bt, lens, LAYER,
                              impl="pallas_interpret")
    mix = ops.paged_mixed_attention(q, pool, bt, last, LAYER,
                                    impl="pallas_interpret")
    _assert_close(mix, dec, params, "mixed_vs_decode")

    c, start, h, kvh, d, page = 8, 5, 4, 2, 16, 8
    cp = (c, start, c, h, kvh, d, page, 1)
    qc, kpc, vpc, btc, s_, v_ = _prefill_case(cp, seed=7)
    poolc = _stack(kpc, vpc)
    chunk = ops.paged_prefill_attention(qc, poolc, btc, s_, v_, LAYER,
                                        impl="pallas_interpret")
    mixc = ops.paged_mixed_attention(
        qc, poolc, jnp.broadcast_to(btc, (c,) + btc.shape),
        jnp.int32(start) + jnp.arange(c, dtype=jnp.int32), LAYER,
        impl="pallas_interpret",
    )
    _assert_close(mixc, chunk, cp, "mixed_vs_chunk")


def test_paged_mixed_structured_xla_matches_oracle():
    """The ``num_decode`` structure hint must not change results: the split
    XLA fallback (decode rows through the decode ref, chunk rows through
    ONE shared-table prefill gather) equals the generic per-row oracle,
    with dead rows — idle decode slots AND chunk padding suffixes — still
    exact zeros."""
    for params, dead_tail in (((7, 4, 2, 16, 8, 3, 1, 4), 2),
                              ((6, 8, 1, 8, 16, 2, 0, 3), 0),
                              ((5, 4, 4, 32, 4, 2, 1, 2), 2)):
        q, kp, vp, bt, last = _mixed_case(params, seed=11)
        r, ck = params[0], params[7]
        last = np.asarray(last).copy()
        if dead_tail:
            last[r - dead_tail:] = -1  # chunk padding: a dead suffix
        last = jnp.asarray(last)
        pool = _stack(kp, vp)
        want = ops.paged_mixed_attention(q, pool, bt, last, LAYER,
                                         impl="xla_chunked")
        got = ops.paged_mixed_attention(q, pool, bt, last, LAYER,
                                        impl="xla_chunked",
                                        num_decode=r - ck)
        _assert_close(got, want, params + (dead_tail,), "mixed_structured")
        dead = np.asarray(last) < 0
        assert (np.asarray(got)[dead] == 0).all(), (
            f"dead rows must be exact zeros at {params}")


# ---------------------------------------------------------------------------
# speculative verify (k drafted tokens scored through the chunk path)
# ---------------------------------------------------------------------------
# params reuse the prefill tuple: (c, start, valid, h, kvh, d, page, extra)
#   c = bundle width W (spec_k+1, padded), valid = 1 + k live rows,
#   start = the sequence's cached length L when the bundle dispatched


def _verify_sweep():
    cases = []
    rng = np.random.default_rng(0x5BEC)
    for k in range(1, 9):  # the engine's full draft-depth range
        start = int(rng.integers(1, 25))
        cases.append((k + 1, start, k + 1, 4, 2, 16, 8, 1))
        if k > 1:  # padded bundle: fewer drafts than the compiled width
            cases.append((k + 2, start, k + 1, 4, 2, 16, 8, 0))
    return cases


@pytest.mark.parametrize("params", _verify_sweep(),
                         ids=lambda p: "c{}s{}v{}h{}k{}d{}p{}x{}".format(*p))
def test_paged_verify_equals_decode_loop(params):
    """The verify contract, end to end: scoring a k-draft bundle through
    the chunk path (``models/lm.py::verify_step_paged`` lowers through
    ``ops.paged_prefill_attention``) must equal BOTH the dedicated
    ``paged_verify_attention_ref`` oracle and a k+1-iteration single-token
    decode loop over the same pages — the unrolled sequential decode the
    bundle replaces. COW-forked tables included: speculation runs on
    post-fork sequences too. Rows past ``valid`` are exact zeros (the
    executor pads every bundle to the compiled width)."""
    for seed, forked in ((0, False), (1, True)):
        q, kp, vp, bt, start, valid = _prefill_case(params, seed, forked)
        want = ref.paged_verify_attention_ref(q, kp, vp, bt, start, valid)
        pool = _stack(kp, vp)
        chunk = ops.paged_prefill_attention(q, pool, bt, start, valid, LAYER,
                                            impl="pallas_interpret")
        _assert_close(chunk, want, params + (seed,), "verify_vs_chunk")
        mixed = ops.paged_mixed_attention(
            q, pool, jnp.broadcast_to(bt, (q.shape[0],) + bt.shape),
            jnp.where(jnp.arange(q.shape[0]) < valid,
                      start + jnp.arange(q.shape[0]), -1).astype(jnp.int32),
            LAYER, impl="pallas_interpret",
        )
        _assert_close(mixed, want, params + (seed,), "verify_vs_mixed")
        for j in range(int(valid)):  # the decode loop the bundle replaces
            dec = ops.paged_attention(
                q[j][None], pool, bt[None],
                jnp.asarray([int(start) + j + 1], jnp.int32), LAYER,
                impl="xla_chunked",
            )[0]
            _assert_close(dec, want[j], params + (seed, j),
                          "verify_vs_decode_loop")
        assert (np.asarray(want)[int(valid):] == 0).all(), (
            f"padded verify rows must be exact zeros at {params}")


def test_paged_verify_invariant_to_page_relocation():
    """Preempt-and-resume re-admits a sequence into different physical
    pages; verify must depend only on table-addressed content, so moving
    a page and repointing the block table cannot change a single logit."""
    params = (5, 11, 5, 4, 2, 16, 8, 1)
    q, kp, vp, bt, start, valid = _prefill_case(params, seed=9)
    base = ref.paged_verify_attention_ref(q, kp, vp, bt, start, valid)
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    bt2 = np.asarray(bt).copy()
    live = [p for p in bt2.tolist() if p != NULL_PAGE]
    spare = next(p for p in range(1, kp2.shape[0]) if p not in live)
    kp2[spare], vp2[spare] = kp2[live[0]], vp2[live[0]]
    bt2[np.asarray(bt).tolist().index(live[0])] = spare
    moved = ops.paged_prefill_attention(
        q, _stack(kp2, vp2), jnp.asarray(bt2),
        start, valid, LAYER, impl="pallas_interpret",
    )
    _assert_close(moved, base, params, "verify_page_relocation")


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def _flash_sweep():
    cases = []
    rng = np.random.default_rng(0xF1A54)
    for _ in range(10):
        bq = int(rng.choice([16, 32, 64]))
        nq = int(rng.integers(1, 4))
        nk = nq + int(rng.integers(0, 3))  # Skv >= Sq (prefill continuation)
        kvh = int(rng.choice([1, 2, 4]))
        cases.append((
            int(rng.integers(1, 3)), bq * nq, bq * nk,
            kvh * int(rng.choice([1, 2])), kvh,
            int(rng.choice([16, 32, 64])), bool(rng.integers(0, 2)), bq,
        ))
    return cases


@pytest.mark.parametrize("params", _flash_sweep(),
                         ids=lambda p: "b{}q{}k{}h{}g{}d{}{}blk{}".format(
                             *p[:6], "c" if p[6] else "f", p[7]))
def test_flash_kernel_vs_oracle(params):
    b, sq, skv, h, kvh, d, causal, blk = params
    rng = np.random.default_rng(sum(params))
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, skv, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, kvh, d)), jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    got = ops.flash_attention(q, k, v, causal=causal, impl="pallas_interpret",
                              block_q=blk, block_kv=blk)
    _assert_close(got, want, params, "flash")


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


def _ssd_sweep():
    cases = []
    rng = np.random.default_rng(0x55D)
    for _ in range(8):
        chunk = int(rng.choice([8, 16, 32]))
        # s NOT necessarily divisible by chunk: exercises the pad-and-mask
        # path (dt=0 tail positions are identities on the recurrence)
        cases.append((
            int(rng.integers(1, 3)), chunk * int(rng.integers(1, 4))
            + int(rng.choice([0, 3])), int(rng.choice([1, 2, 4])),
            int(rng.choice([8, 16])), int(rng.choice([16, 32])), chunk,
        ))
    return cases


@pytest.mark.parametrize("params", _ssd_sweep(),
                         ids=lambda p: "b{}s{}h{}p{}n{}c{}".format(*p))
def test_ssd_kernel_vs_oracle(params):
    b, s, h, p, n, chunk = params
    rng = np.random.default_rng(sum(params))
    x = rng.standard_normal((b, s, h, p)).astype(np.float32)
    dt = (0.1 + 0.9 * rng.random((b, s, h))).astype(np.float32)
    A = (-1.0 * rng.random((h,)) - 0.1).astype(np.float32)
    Bm = (rng.standard_normal((b, s, n)) / np.sqrt(n)).astype(np.float32)
    Cm = (rng.standard_normal((b, s, n)) / np.sqrt(n)).astype(np.float32)
    y_want, st_want = ref.ssd_sequential(x, dt, A, Bm, Cm)
    y_got, st_got = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                                 impl="pallas_interpret")
    _assert_close(y_got, y_want, params, "ssd_y")
    _assert_close(st_got, st_want, params, "ssd_state")


@pytest.mark.parametrize("params", _ssd_sweep(),
                         ids=lambda p: "b{}s{}h{}p{}n{}c{}".format(*p))
def test_ssd_kernel_vs_oracle_with_init_state(params):
    """Carried-state continuation (chunked serving prefill): the kernel path
    must thread ``init_state`` exactly like the literal recurrence, on the
    same non-chunk-multiple lengths as the fresh-state sweep."""
    b, s, h, p, n, chunk = params
    rng = np.random.default_rng(sum(params) ^ 0x1517)
    x = rng.standard_normal((b, s, h, p)).astype(np.float32)
    dt = (0.1 + 0.9 * rng.random((b, s, h))).astype(np.float32)
    A = (-1.0 * rng.random((h,)) - 0.1).astype(np.float32)
    Bm = (rng.standard_normal((b, s, n)) / np.sqrt(n)).astype(np.float32)
    Cm = (rng.standard_normal((b, s, n)) / np.sqrt(n)).astype(np.float32)
    h0 = rng.standard_normal((b, h, p, n)).astype(np.float32)
    y_want, st_want = ref.ssd_sequential(x, dt, A, Bm, Cm, init_state=h0)
    y_got, st_got = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk,
                                 impl="pallas_interpret", init_state=h0)
    _assert_close(y_got, y_want, params, "ssd_y_h0")
    _assert_close(st_got, st_want, params, "ssd_state_h0")


def _ssd_decode_sweep():
    cases = []
    rng = np.random.default_rng(0xDECD)
    for _ in range(6):
        cases.append((int(rng.integers(1, 5)), int(rng.choice([1, 2, 4, 8])),
                      int(rng.choice([8, 16, 64])), int(rng.choice([16, 32]))))
    return cases


def _ssd_decode_case(params, seed):
    b, h, p, n = params
    rng = np.random.default_rng(seed + sum(params))
    state = rng.standard_normal((b, h, p, n)).astype(np.float32)
    x_t = rng.standard_normal((b, h, p)).astype(np.float32)
    dt_t = (0.1 + 0.9 * rng.random((b, h))).astype(np.float32)
    A = (-1.0 * rng.random((h,)) - 0.1).astype(np.float32)
    B_t = (rng.standard_normal((b, n)) / np.sqrt(n)).astype(np.float32)
    C_t = (rng.standard_normal((b, n)) / np.sqrt(n)).astype(np.float32)
    return state, x_t, dt_t, A, B_t, C_t


@pytest.mark.parametrize("params", _ssd_decode_sweep(),
                         ids=lambda p: "b{}h{}p{}n{}".format(*p))
def test_ssd_decode_step_kernel_vs_oracle(params):
    args = _ssd_decode_case(params, 0)
    y_want, st_want = ref.ssd_decode_step(*args)
    y_got, st_got = ops.ssd_decode_step(*args, impl="pallas_interpret")
    _assert_close(y_got, y_want, params, "ssd_dec_y")
    _assert_close(st_got, st_want, params, "ssd_dec_state")


# ---------------------------------------------------------------------------
# int8 quantized KV pages (fused-dequant kernel variants)
# ---------------------------------------------------------------------------
# Two distinct bounds, asserted separately:
#  - kernel parity: the fused-dequant Pallas kernel vs dequantize_pages +
#    the unchanged fp32 oracle over the SAME int8 pool must agree to TOL —
#    quantization itself contributes zero error to this comparison.
#  - quantization error: int8 attention vs the original fp32 pool. Each KV
#    element carries at most scale/2 ≈ absmax/254 absolute error; through
#    the softmax-weighted sum the V error passes via a convex combination
#    (bounded by max per-row V error) and the K error perturbs logits by
#    O(|q|·d·scale/2), so for unit-normal inputs the observed output error
#    is ~1e-2. QTOL below holds 4x margin over the sweep's observed max.

QTOL = 8e-2  # int8-vs-fp32 attention output bound (observed ~2e-2)


def _quantize_pool(kp, vp):
    kq, ks = ref.quantize_kv(kp)
    vq, vs = ref.quantize_kv(vp)
    return kq, ks, vq, vs


@pytest.mark.parametrize("params", _PREFILL_EDGES,
                         ids=lambda p: "c{}s{}v{}h{}k{}d{}p{}x{}".format(*p))
def test_paged_prefill_quantized_kernel_vs_oracle(params):
    q, kp, vp, bt, start, valid = _prefill_case(params, seed=0)
    kq, ks, vq, vs = _quantize_pool(kp, vp)
    want = ref.paged_prefill_attention_ref(
        q, ref.dequantize_pages(kq, ks), ref.dequantize_pages(vq, vs),
        bt, start, valid,
    )
    pool, sc = _stack_q(kq, ks, vq, vs)
    got = ops.paged_prefill_attention(
        q, pool, bt, start, valid, LAYER, kv_scale=sc,
        impl="pallas_interpret",
    )
    _assert_close(got, want, params, "paged_prefill_q")
    # quantization error vs the original fp32 pool: the documented bound
    fp32 = ref.paged_prefill_attention_ref(q, kp, vp, bt, start, valid)
    err = float(jnp.abs(got - fp32).max())
    assert err <= QTOL, f"paged_prefill int8-vs-fp32 err={err:.3e} > {QTOL}"


@pytest.mark.parametrize("params", _decode_sweep()[:8],
                         ids=lambda p: "b{}h{}k{}d{}p{}m{}{}".format(
                             *p[:6], "a" if p[6] else ""))
def test_paged_decode_quantized_kernel_vs_oracle(params):
    q, kp, vp, bt, lens = _decode_case(params, seed=0)
    kq, ks, vq, vs = _quantize_pool(kp, vp)
    want = ops.paged_attention(
        q, _stack(ref.dequantize_pages(kq, ks), ref.dequantize_pages(vq, vs)),
        bt, lens, LAYER, impl="xla_chunked",
    )
    pool, sc = _stack_q(kq, ks, vq, vs)
    got = ops.paged_attention(q, pool, bt, lens, LAYER, kv_scale=sc,
                              impl="pallas_interpret")
    _assert_close(got, want, params, "paged_decode_q")
    fp32 = ops.paged_attention(q, _stack(kp, vp), bt, lens, LAYER,
                               impl="xla_chunked")
    err = float(jnp.abs(got - fp32).max())
    assert err <= QTOL, f"paged_decode int8-vs-fp32 err={err:.3e} > {QTOL}"
    if int(lens[0]) == 0:
        assert (np.asarray(got)[0] == 0).all(), "idle slot must stay zero"


@pytest.mark.parametrize("params", _mixed_sweep()[:8],
                         ids=lambda p: "r{}h{}k{}d{}p{}m{}x{}c{}".format(*p))
def test_paged_mixed_quantized_kernel_vs_oracle(params):
    q, kp, vp, bt, last = _mixed_case(params, seed=0)
    kq, ks, vq, vs = _quantize_pool(kp, vp)
    want = ops.paged_mixed_attention(
        q, _stack(ref.dequantize_pages(kq, ks), ref.dequantize_pages(vq, vs)),
        bt, last, LAYER, impl="xla_chunked",
    )
    pool, sc = _stack_q(kq, ks, vq, vs)
    got = ops.paged_mixed_attention(q, pool, bt, last, LAYER, kv_scale=sc,
                                    impl="pallas_interpret")
    _assert_close(got, want, params, "paged_mixed_q")
    fp32 = ops.paged_mixed_attention(q, _stack(kp, vp), bt, last, LAYER,
                                     impl="xla_chunked")
    err = float(jnp.abs(got - fp32).max())
    assert err <= QTOL, f"paged_mixed int8-vs-fp32 err={err:.3e} > {QTOL}"
    dead = np.asarray(last) < 0
    assert (np.asarray(got)[dead] == 0).all(), "dead rows must stay zero"


def test_quantize_dequant_roundtrip_grid():
    """Deterministic always-run slice of the round-trip property below."""
    for rows, kvh, d, scale_exp, seed in [
        (1, 1, 4, 0, 0), (16, 2, 8, -8, 1), (40, 4, 32, 8, 2),
        (7, 1, 16, -3, 3), (24, 2, 4, 5, 4),
    ]:
        _roundtrip_check(rows, kvh, d, scale_exp, seed)


def _roundtrip_check(rows, kvh, d, scale_exp, seed):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((rows, kvh, d)) * 2.0 ** scale_exp).astype(
        np.float32)
    x[0] = 0.0
    q, scale = ref.quantize_kv(jnp.asarray(x))
    back = np.asarray(ref.dequantize_pages(q, scale))
    bound = np.asarray(scale)[..., None] / 2 + 1e-9
    assert (np.abs(back - x) <= bound).all()
    assert (back[0] == 0).all()


@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(1, 40),
    kvh=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([4, 8, 16, 32]),
    scale_exp=st.integers(-8, 8),
    seed=st.integers(0, 2**16),
)
def test_quantize_dequant_roundtrip_bound(rows, kvh, d, scale_exp, seed):
    """quantize_kv -> dequantize_pages recovers every element to within
    scale/2 (the round-to-nearest half step), across magnitudes 2^-8..2^8,
    and all-zero rows survive the scale clamp exactly."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((rows, kvh, d)) * 2.0 ** scale_exp).astype(
        np.float32)
    x[0] = 0.0  # always include an all-zero row
    q, scale = ref.quantize_kv(jnp.asarray(x))
    assert q.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    back = np.asarray(ref.dequantize_pages(q, scale))
    bound = np.asarray(scale)[..., None] / 2 + 1e-9
    assert (np.abs(back - x) <= bound).all(), (
        f"round-trip exceeded scale/2 at rows={rows} d={d} 2^{scale_exp}")
    assert (back[0] == 0).all()


# ---------------------------------------------------------------------------
# off-TPU policy: compiled impl="pallas" raises, for every op
# ---------------------------------------------------------------------------


def _pallas_off_tpu_calls():
    """One small call per Pallas-backed op, keyed by op name."""
    q, kp, vp, bt, start, valid = _prefill_case((4, 4, 4, 4, 2, 16, 8, 1), 0)
    qd, kpd, vpd, btd, lens = _decode_case((2, 4, 2, 16, 8, 2, False), 0)
    qm, kpm, vpm, btm, last = _mixed_case((4, 4, 2, 8, 4, 3, 1, 2), 0)
    x = jnp.ones((1, 16, 2, 8), jnp.float32)
    dt = jnp.full((1, 16, 2), 0.1, jnp.float32)
    a = -jnp.ones((2,), jnp.float32)
    bm = jnp.ones((1, 16, 8), jnp.float32)
    fq = jnp.ones((1, 16, 2, 8), jnp.float32)
    return {
        "flash_attention": lambda: ops.flash_attention(
            fq, fq, fq, impl="pallas"),
        "paged_attention": lambda: ops.paged_attention(
            qd, _stack(kpd, vpd), btd, lens, LAYER, impl="pallas"),
        "paged_prefill_attention": lambda: ops.paged_prefill_attention(
            q, _stack(kp, vp), bt, start, valid, LAYER, impl="pallas"),
        "paged_mixed_attention": lambda: ops.paged_mixed_attention(
            qm, _stack(kpm, vpm), btm, last, LAYER, impl="pallas"),
        "ssd_scan": lambda: ops.ssd_scan(x, dt, a, bm, bm, impl="pallas"),
        "ssd_decode_step": lambda: ops.ssd_decode_step(
            *_ssd_decode_case((2, 4, 16, 32), 0), impl="pallas"),
    }


@pytest.mark.parametrize("op", [
    "flash_attention", "paged_attention", "paged_prefill_attention",
    "paged_mixed_attention", "ssd_scan", "ssd_decode_step",
])
def test_pallas_off_tpu_raises(op):
    """Off-TPU, ``impl='pallas'`` must raise for every op — never serve
    through the reference in silence — while "auto" keeps resolving to the
    XLA path there."""
    if jax.default_backend() == "tpu":
        pytest.skip("the raise only exists off-TPU")
    call = _pallas_off_tpu_calls()[op]
    with pytest.raises(ops.PallasUnavailableError, match=op):
        call()
    assert ops._resolve_pallas_impl("auto", False, op) == ("xla_chunked", False)
    assert ops._resolve_pallas_impl("pallas_interpret", False, op) == (
        "pallas", True)
