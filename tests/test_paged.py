"""Paged KV cache + async admission tests (ISSUE 7).

Equivalence contract (the PR's guarantee):
  * token streams are EXACTLY equal between the dense per-slot cache and
    the paged (block-table) cache, and between ``admit_mode`` batched /
    async, for every smoke arch — the paged softmax pads its denominator
    to the dense max_seq (``pad_sum_to``) so attention over a narrowed
    page view is bitwise the dense computation, and per-(seed, rid,
    token-index) sampling keys make streams independent of admission
    interleaving. Families without a paged layout (MLA latents,
    recurrent state) silently pass through on the dense layout.
  * pages are a recycled resource: release/preempt/retire return a
    slot's pages to the free list, admission reserves a request's FULL
    contract up front (reject when it can never fit, WAIT — never evict
    — when the pool is transiently exhausted), and admission failures
    roll back without leaking a page.

Kernel-level paged attention (Pallas scalar-prefetch block-table
kernels) is pinned against the dense oracle here too; the engine-level
tests above exercise the XLA fallback paths the smoke shapes take.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels import ops, ref
from repro.models import transformer as T
from repro.models.api import build
from repro.serving import engine as engine_mod
from repro.serving.engine import Request, ServingEngine

ARCHS = ["llama3.2-1b", "qwen3-14b", "phi3.5-moe-42b-a6.6b", "rwkv6-1.6b",
         "deepseek-v2-236b", "zamba2-7b", "seamless-m4t-medium",
         "paligemma-3b"]
DENSE_ONLY = {"rwkv6-1.6b", "zamba2-7b", "deepseek-v2-236b"}


@pytest.fixture
def assert_compile_bounds():
    """Compile-cache budget for the paged engine: extends are always
    dispatched at the FULL table width over the pow-2 cover of the rows
    that take the chunk, so paged-extend variants are keyed by chunk
    size and row count — O(log max_seq) x O(log max_batch) entries;
    decode runs at the pow-2 page cover of the longest live row —
    O(log maxP) variants. An unbounded cache here means per-width
    recompiles in production serving."""
    def check(eng):
        n_seq = int(math.log2(eng.max_seq)) + 1
        n_bat = int(math.log2(eng.max_batch)) + 1
        n_pages = int(math.log2(max(eng.max_seq // eng.page_size, 1))) + 1
        for fn, bound in ((getattr(eng, "_extend_paged", None),
                           n_seq * n_bat),
                          (eng._decode, n_pages),
                          (getattr(eng, "_decode_masked", None), n_pages)):
            if fn is not None and hasattr(fn, "_cache_size"):
                assert fn._cache_size() <= bound, \
                    f"{fn} compiled {fn._cache_size()} > {bound} variants"
    return check


def _build(arch):
    cfg = smoke_config(arch)
    model = build(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


def _engine(model, params, mode="batched", **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 64)
    return ServingEngine(model, params, admit_mode=mode, **kw)


def _requests(cfg, seed=0, lengths=(8, 13, 5, 11, 7, 9), n_new=4,
              temps=(0.0, 0.7, 0.0, 1.3, 0.0, 0.7)):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=n_new, temperature=t)
            for i, (n, t) in enumerate(zip(lengths, temps))]


# ------------------------------------------------------------ equivalence
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_matches_dense_all_archs(arch):
    """Dense vs paged engine: bitwise-equal token streams under both
    batched and async admission; MLA/recurrent archs silently stay on
    the dense layout (``supports_paged_cache``)."""
    cfg, model, params = _build(arch)
    streams = {}
    for name, kw in (("dense", {}),
                     ("paged", {"paged": True}),
                     ("paged_async", {"paged": True})):
        mode = "async" if name.endswith("async") else "batched"
        eng = _engine(model, params, mode, **kw)
        if kw.get("paged"):
            assert eng.paged == (arch not in DENSE_ONLY)
        for r in _requests(cfg):
            eng.submit(r)
        m = eng.run()
        assert m.summary()["num_completed"] == 6
        streams[name] = {r.rid: list(r.tokens) for r in m.completed}
        assert eng.reconcile()["balanced"]
        if eng.paged:
            assert len(eng._free_pages) == eng.num_pages
            assert all(not p for p in eng.slot_pages)
    assert streams["paged"] == streams["dense"]
    assert streams["paged_async"] == streams["dense"]


def test_paged_cache_bits_match_dense():
    """At the admission snapshot the paged pool, gathered through the
    block tables, holds bit-identical KV to the dense cache rows (the
    stream equality above could in principle hide compensating
    errors; this pins the cache itself)."""
    cfg, model, params = _build("llama3.2-1b")
    caches = {}
    for name, kw in (("dense", {}), ("paged", {"paged": True})):
        eng = _engine(model, params, **kw)
        for r in _requests(cfg):
            eng.submit(r)
        eng._admit()
        if name == "paged":
            tab = jnp.asarray(eng._tbl)
            gathered = jax.tree.map(
                lambda pool: jnp.stack(
                    [ref.paged_gather_ref(pool[l], tab)
                     for l in range(pool.shape[0])]),
                eng.cache["kv"])
            caches[name] = (jax.tree.map(np.asarray, gathered),
                            np.asarray(eng.cache["pos"]))
        else:
            caches[name] = (jax.tree.map(np.asarray, eng.cache["kv"]),
                            np.asarray(eng.cache["pos"]))
    (dk, dpos), (pk, ppos) = caches["dense"], caches["paged"]
    assert (dpos == ppos).all()
    for a, b in zip(jax.tree.leaves(dk), jax.tree.leaves(pk)):
        S = min(a.shape[2], b.shape[2])
        valid = np.arange(S)[None, :] < dpos[:, None]       # [B, S]
        m = valid[None, :, :, None, None]
        np.testing.assert_array_equal(
            np.where(m, a[:, :, :S], 0), np.where(m, b[:, :, :S], 0))


def test_paged_int8_cache_matches_dense_int8():
    """Model-level extend + decode over an int8 PAGED cache is bitwise
    the int8 DENSE path: quantization happens on the same chunk values,
    and the paged softmax pads to the dense denominator."""
    cfg, model, params = _build("llama3.2-1b")
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)

    dense = T.make_decode_cache(cfg, 1, 64)
    dense = jax.tree.map(jnp.zeros_like, dense)
    dense = T.quantize_decode_cache(dense)
    paged = T.make_paged_decode_cache(cfg, 1, 64, page_size=16,
                                      dtype="int8")
    paged["table"] = jnp.arange(4, dtype=jnp.int32)[None]   # identity map

    chunk = {"tokens": jnp.asarray(prompt)[None]}
    ld, dense = model.extend_fn(params, chunk, dense)
    lp, paged = model.extend_fn(params, chunk, paged)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(lp))
    tok = jnp.argmax(ld, -1).astype(jnp.int32)      # extend_fn returns [B, V]
    ld2, dense = model.decode_fn(params, {"token": tok}, dense)
    lp2, paged = model.decode_fn(params, {"token": tok}, paged)
    np.testing.assert_array_equal(np.asarray(ld2), np.asarray(lp2))
    assert paged["kv"]["k"].dtype == jnp.int8
    assert "k_scale" in paged["kv"]


@pytest.mark.parametrize("takers", [[1, 3], [0, 2, 3]])
def test_compacted_extend_int8_pool_matches_full_batch(takers):
    """The engine's paged extend over only the taking rows (padded to a
    power of two with a sentinel row) is bitwise the full-batch call with
    the other rows' tables sentineled, on an int8 page pool: the same
    logits per taker, the same pools, and ``pos`` advanced for the takers
    alone."""
    cfg, model, params = _build("qwen3-14b")
    B, S, page = 4, 64, 16
    eng = _engine(model, params, paged=True, max_batch=B, max_seq=S,
                  page_size=page)
    cache = T.make_paged_decode_cache(cfg, B, S, page_size=page,
                                      dtype="int8")
    P, maxP = B * (S // page), S // page
    table = np.arange(P, dtype=np.int32).reshape(B, maxP)
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, cfg.vocab_size, size=(B, 12)).astype(np.int32)
    _, cache = model.extend_fn(params, {"tokens": jnp.asarray(prompt)},
                               {**cache, "table": jnp.asarray(table)})
    chunk = rng.integers(0, cfg.vocab_size, size=(B, 4)).astype(np.int32)
    take = np.isin(np.arange(B), takers)
    lf, full = model.extend_fn(
        params, {"tokens": jnp.asarray(chunk)},
        {**cache, "table": jnp.asarray(np.where(take[:, None], table, P))})

    R = 1 << (len(takers) - 1).bit_length()
    rows = np.full((R,), B, np.int32)
    rows[:len(takers)] = takers
    toks = np.zeros((R, 4), np.int32)
    toks[:len(takers)] = chunk[takers]
    tbl = np.full((R, maxP), P, np.int32)
    tbl[:len(takers)] = table[takers]
    lc, comp = eng._extend_paged(params, jnp.asarray(toks),
                                 jnp.asarray(rows),
                                 {**cache, "table": jnp.asarray(tbl)})
    assert lc.shape[0] == R
    np.testing.assert_array_equal(np.asarray(lc)[:len(takers)],
                                  np.asarray(lf)[takers])
    assert comp["kv"]["k"].dtype == jnp.int8 and "k_scale" in comp["kv"]
    for a, b in zip(jax.tree.leaves(comp["kv"]), jax.tree.leaves(full["kv"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(comp["pos"]),
                                  np.where(take, 16, 12))


# ------------------------------------------------------- page accounting
def test_page_recycling_across_waves(assert_compile_bounds):
    """Pages freed by retiring requests are reused by later waves: a
    3-wave workload through a pool that only fits one wave at a time
    completes with the full free list restored, and the compile cache
    stays within the O(log) budget."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, paged=True, num_pages=8)   # 128 tokens
    rng = np.random.default_rng(2)
    for i in range(9):                       # each needs 2 pages -> 3 waves
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=6))
    saw_exhausted = False
    while True:
        n = eng.step()
        saw_exhausted |= (not eng._free_pages and bool(eng.waiting))
        if n == 0 and not eng.waiting and not eng._pend:
            break
    assert saw_exhausted                      # the pool really was the limit
    assert eng.metrics.summary()["num_completed"] == 9
    assert sorted(eng._free_pages) == list(range(eng.num_pages))
    assert all(not p for p in eng.slot_pages)
    assert (eng._tbl == eng.num_pages).all()  # tables fully sentineled
    assert eng.reconcile()["balanced"]
    assert_compile_bounds(eng)


def test_preempt_resume_recycles_and_replays_pages():
    """Preempting a paged slot returns its pages; resuming re-reserves
    (possibly different) pages and the stream continues bitwise (the
    fault-tolerance contract on the paged layout)."""
    cfg, model, params = _build("llama3.2-1b")
    rng = np.random.default_rng(3)
    mk = lambda: Request(rid=5, prompt=rng.integers(
        0, cfg.vocab_size, size=10).astype(np.int32), max_new_tokens=8,
        temperature=0.9)
    ref_eng = _engine(model, params, paged=True)
    r0 = mk()
    rng = np.random.default_rng(3)
    ref_eng.submit(r0)
    ref_eng.run()

    eng = _engine(model, params, paged=True)
    rng = np.random.default_rng(3)
    eng.submit(mk())
    for _ in range(3):
        eng.step()
    snap, = eng.preempt()
    assert len(eng._free_pages) == eng.num_pages     # pages back on preempt
    assert eng.resume(snap) is not None
    m = eng.run()
    assert [list(r.tokens) for r in m.completed] == [list(r0.tokens)]
    assert len(eng._free_pages) == eng.num_pages


def test_fragmented_free_list_still_serves():
    """Adversarial fragmentation: interleaved release orders scramble the
    free list, so later admissions get non-contiguous physical pages —
    streams must still match a fresh dense engine bitwise."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, paged=True, num_pages=12)
    rng = np.random.default_rng(6)
    lens = [9, 17, 5, 21]
    first = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=n).astype(np.int32), max_new_tokens=3)
        for i, n in enumerate(lens)]
    for r in first:
        eng.submit(r)
    eng.step()
    eng.preempt(slots=[1, 3])                # scramble: free middle slots
    eng.run()
    assert sorted(eng._free_pages) == list(range(12))
    assert eng._free_pages != list(range(11, -1, -1))   # really scrambled
    second = [Request(rid=10 + i, prompt=rng.integers(
        0, cfg.vocab_size, size=n).astype(np.int32), max_new_tokens=3)
        for i, n in enumerate([21, 9, 17])]
    for r in second:
        eng.submit(r)
    m = eng.run()
    got = {r.rid: list(r.tokens) for r in m.completed if r.rid >= 10}

    dense = _engine(model, params)
    for r in second:
        r.tokens, r.prefill_done_s, r.finish_s = [], None, None
        dense.submit(r)
    md = dense.run()
    want = {r.rid: list(r.tokens) for r in md.completed}
    assert got == want


# --------------------------------------------- reject / wait / rollback
def test_reject_when_pages_can_never_fit():
    """A contract needing more pages than the pool will EVER have is
    rejected up front (not deadlocked waiting); one that only
    transiently doesn't fit waits and completes."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, paged=True, num_pages=2)   # 32 tokens
    rng = np.random.default_rng(7)
    eng.submit(Request(rid=0, prompt=rng.integers(          # needs 3 pages
        0, cfg.vocab_size, size=30).astype(np.int32), max_new_tokens=4))
    eng.submit(Request(rid=1, prompt=rng.integers(          # fits: 2 pages
        0, cfg.vocab_size, size=14).astype(np.int32), max_new_tokens=4))
    eng.submit(Request(rid=2, prompt=rng.integers(          # waits for rid 1
        0, cfg.vocab_size, size=14).astype(np.int32), max_new_tokens=4))
    eng.step()
    assert [r.rid for r in eng.metrics.rejected] == [0]
    assert [r.rid for r in eng.waiting] == [2]              # waiting, not shed
    m = eng.run()
    assert sorted(r.rid for r in m.completed) == [1, 2]
    assert eng.reconcile()["balanced"]


def test_no_page_leak_on_admission_error(monkeypatch):
    """An exception mid-admission (injected at the page-pool insert)
    rolls back: every reserved page returns to the free list, tables are
    re-sentineled, and the requests requeue."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, paged=True)
    rng = np.random.default_rng(8)
    for i in range(2):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=8).astype(np.int32), max_new_tokens=3))

    def boom(*a, **k):
        raise RuntimeError("injected page insert failure")

    monkeypatch.setattr(engine_mod, "insert_cache_pages", boom)
    with pytest.raises(RuntimeError, match="injected"):
        eng.step()
    assert sorted(eng._free_pages) == list(range(eng.num_pages))
    assert (eng._tbl == eng.num_pages).all()
    assert all(r is None for r in eng.active) and not eng._pend
    assert [r.rid for r in eng.waiting] == [0, 1]
    monkeypatch.undo()
    m = eng.run()
    assert m.summary()["num_completed"] == 2
    assert len(eng._free_pages) == eng.num_pages


# --------------------------------------------------------- async engine
def test_async_interleaves_and_bounds_per_step_work(assert_compile_bounds):
    """Async admission: a long prompt streams in as budgeted arbiter
    chunks while an already-live request KEEPS DECODING (the pending
    slot is row-masked out of decode) — the no-stall property batched
    admission lacks — and the utilization counters surface it."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, "async", paged=True, admit_token_budget=8)
    rng = np.random.default_rng(9)
    eng.submit(Request(rid=1, prompt=rng.integers(                # short,
        0, cfg.vocab_size, size=8).astype(np.int32),              # pow-2:
        max_new_tokens=8))                                        # no tail
    eng.step()                                # rid 1 live and decoding
    live1 = next(r for r in eng.active if r is not None and r.rid == 1)
    eng.submit(Request(rid=0, prompt=rng.integers(                # long
        0, cfg.vocab_size, size=47).astype(np.int32), max_new_tokens=3))
    decoded_while_pending = 0
    for _ in range(4):
        before = len(live1.tokens)
        eng.step()
        if eng._pend and len(live1.tokens) > before:
            decoded_while_pending += 1
    assert decoded_while_pending >= 1         # real prefill/decode overlap
    m = eng.run()
    assert m.summary()["num_completed"] == 2
    s = m.summary()
    assert s["extend_chunks"] >= 2            # arbiter really chunked it
    assert s["decode_steps"] > 0
    util = eng.reconcile()["decode_utilization"]
    assert util["decode_steps"] == s["decode_steps"]
    assert_compile_bounds(eng)


def test_extend_leaves_other_pages_untouched(assert_compile_bounds):
    """Async paged admission of three overlapping tails (15, 14, 12 =
    bucket 8 + chunks 4+2+1, 4+2, 4) beside a live decoding request:
    across every extend dispatch (three takers in four rows, then two,
    then one) no page but the takers' changes, so the live slot's pages
    and the free pages are bitwise as they were. The streams are pinned
    against serial and dense in ``test_serving_batched``."""
    cfg, model, params = _build("qwen3-14b")
    rng = np.random.default_rng(11)
    first, *wave = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=n).astype(np.int32), max_new_tokens=new)
        for i, (n, new) in enumerate(((8, 12), (15, 4), (14, 4), (12, 4)))]
    eng = _engine(model, params, "async", paged=True)
    seen = []
    inner = eng._dispatch_extend

    def spy(chunks, C):
        takers = [slot for slot, _ in chunks]
        mine = [p for s in takers for p in eng.slot_pages[s]]
        others = [p for p in range(eng.num_pages) if p not in mine]
        live = [p for s, r in enumerate(eng.active) if r is not None
                for p in eng.slot_pages[s]]
        before = jax.tree.map(np.asarray, eng.cache["kv"])
        logits, at = inner(chunks, C)
        after = jax.tree.map(np.asarray, eng.cache["kv"])
        seen.append((len(takers), logits.shape[0]))
        assert live and eng._free_pages
        assert set(live) | set(eng._free_pages) <= set(others)
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            np.testing.assert_array_equal(a[:, others], b[:, others])
        assert any((a[:, mine] != b[:, mine]).any() for a, b in
                   zip(jax.tree.leaves(before), jax.tree.leaves(after)))
        return logits, at

    eng._dispatch_extend = spy
    eng.submit(first)
    eng.step()
    for r in wave:
        eng.submit(r)
    assert eng.run().summary()["num_completed"] == 4
    assert seen == [(3, 4), (2, 2), (1, 1)]
    assert eng.reconcile()["balanced"]
    assert_compile_bounds(eng)


def test_async_matches_serial_with_deadlines_and_brownout():
    """Async + paged under the full control surface (deadline sweeps on
    pending slots, brownout shed) still reconciles; a pending slot past
    its deadline is swept without leaking its reserved pages."""
    cfg, model, params = _build("llama3.2-1b")
    clk = [0.0]
    eng = _engine(model, params, "async", paged=True, admit_token_budget=8,
                  clock=lambda: clk[0])
    rng = np.random.default_rng(10)
    eng.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=40).astype(np.int32), max_new_tokens=5,
        deadline_s=5.0))
    eng.step()
    assert eng._pend                          # mid-stream, pages reserved
    clk[0] = 10.0                             # deadline passes mid-prefill
    eng.run()
    assert [r.rid for r in eng.metrics.timed_out] == [0]
    assert len(eng._free_pages) == eng.num_pages
    assert not eng._pend and eng.reconcile()["balanced"]


# ------------------------------------------------------- paged kernels
def _mk_paged(rng, B, S, page, KVH, hd):
    maxP = S // page
    P = B * maxP
    lengths = rng.integers(1, S + 1, size=B).astype(np.int32)
    perm = list(rng.permutation(P))
    table = np.full((B, maxP), P, np.int32)
    for b in range(B):
        for j in range(-(-int(lengths[b]) // page)):
            table[b, j] = perm.pop()
    kd = rng.standard_normal((B, S, KVH, hd)).astype(np.float32)
    vd = rng.standard_normal((B, S, KVH, hd)).astype(np.float32)
    k_pool = np.zeros((P, page, KVH, hd), np.float32)
    v_pool = np.zeros((P, page, KVH, hd), np.float32)
    for b in range(B):
        for j in range(maxP):
            if table[b, j] < P:
                k_pool[table[b, j]] = kd[b, j * page:(j + 1) * page]
                v_pool[table[b, j]] = vd[b, j * page:(j + 1) * page]
    return (jnp.asarray(kd), jnp.asarray(vd), jnp.asarray(k_pool),
            jnp.asarray(v_pool), jnp.asarray(table), jnp.asarray(lengths))


@pytest.mark.parametrize("B,S,page,KVH,H,hd",
                         [(4, 64, 16, 2, 8, 64), (3, 64, 8, 1, 6, 16)])
def test_paged_decode_kernel_matches_dense_oracle(B, S, page, KVH, H, hd):
    """Pallas block-table decode (scalar-prefetch page gather) against
    the dense ragged oracle, over a permutation-allocated pool."""
    rng = np.random.default_rng(0)
    kd, vd, kp, vp, tab, lens = _mk_paged(rng, B, S, page, KVH, hd)
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    want = ref.decode_attention_ref(q, kd, vd, lens)
    got = ops.paged_decode_attention(q, kp, vp, tab, lens, interpret=True)
    assert float(jnp.abs(got - want).max()) < 2e-5
    got_x = ops.paged_decode_attention(q, kp, vp, tab, lens,
                                       use_pallas=False)
    assert float(jnp.abs(got_x - want).max()) < 2e-5


def test_paged_decode_kernel_int8_fused_dequant():
    """int8 pools + per-(page, token, head) scales: the kernel's fused
    dequant matches the gather-dequant XLA reference."""
    rng = np.random.default_rng(1)
    B, S, page, KVH, H, hd = 4, 64, 16, 2, 8, 64
    _, _, kp, vp, tab, lens = _mk_paged(rng, B, S, page, KVH, hd)
    ks = jnp.abs(kp).max(axis=-1) / 127.0 + 1e-8
    vs = jnp.abs(vp).max(axis=-1) / 127.0 + 1e-8
    kq = jnp.clip(jnp.round(kp / ks[..., None]), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vp / vs[..., None]), -127, 127).astype(jnp.int8)
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    want = ops.paged_decode_attention(q, kq, vq, tab, lens, k_scale=ks,
                                      v_scale=vs, use_pallas=False)
    got = ops.paged_decode_attention(q, kq, vq, tab, lens, k_scale=ks,
                                     v_scale=vs, interpret=True)
    assert float(jnp.abs(got - want).max()) < 2e-4


@pytest.mark.parametrize("B,S,page,KVH,H,hd,C",
                         [(4, 64, 16, 2, 8, 64, 16), (3, 64, 8, 1, 6, 16, 8)])
def test_paged_extend_kernel_matches_oracle(B, S, page, KVH, H, hd, C):
    """Chunked prefill continued from paged cache: the kernel streams
    the cached pages then folds the chunk's own K/V under the causal
    triangle — against the two-einsum oracle."""
    rng = np.random.default_rng(2)
    _, _, kp, vp, tab, lens = _mk_paged(rng, B, S, page, KVH, hd)
    q = jnp.asarray(rng.standard_normal((B, C, H, hd)).astype(np.float32))
    kn = jnp.asarray(rng.standard_normal((B, C, KVH, hd)).astype(np.float32))
    vn = jnp.asarray(rng.standard_normal((B, C, KVH, hd)).astype(np.float32))
    want = ref.paged_extend_attention_ref(q, kp, vp, kn, vn, tab, lens)
    got = ops.paged_extend_attention(q, kp, vp, kn, vn, tab, lens,
                                     interpret=True)
    assert float(jnp.abs(got - want).max()) < 2e-5
