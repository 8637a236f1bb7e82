"""Batched admission pipeline tests: serial/batched equivalence, bursts.

Equivalence contract (the PR's guarantee):
  * token streams are EXACTLY equal between ``admit_mode="batched"`` (grouped
    prefill + descending-pow2 extend tails) and ``admit_mode="serial"`` (the
    reference: one request at a time, B=1 decode tail), for every cache
    family — per-request sampling keys make the draw independent of
    admission order and batch composition, so this holds bitwise even for
    temperature-sampled rows;
  * engine caches agree to numerical tolerance: bitwise for GQA-family KV
    (verified empirically — bf16 rounding absorbs reduction-order ulps),
    ~1e-7 for fp32 recurrent state, and bf16-resolution for MLA, whose
    prefill runs the expanded form while extend runs the absorbed form
    (mathematically equal, different contraction order).

``tests/test_serving.py::test_engine_matches_standalone_decode`` pins the
other side: batched admission vs a standalone full-length B=1 prefill →
decode loop, over all eight smoke archs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models.api import build
from repro.serving import engine as engine_mod
from repro.serving.engine import Request, ServingEngine

# one arch per cache structure: GQA KV, MLA latent + MoE, pure recurrent,
# hybrid state+attn, enc-dec dual cache
FAMILY_ARCHS = ["llama3.2-1b", "deepseek-v2-236b", "rwkv6-1.6b",
                "zamba2-7b", "seamless-m4t-medium"]


def _build(arch):
    cfg = smoke_config(arch)
    model = build(cfg)
    params = model.init_params(jax.random.key(0))
    return cfg, model, params


def _engine(model, params, mode, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq", 64)
    return ServingEngine(model, params, admit_mode=mode, **kw)


def _requests(cfg, seed=0, lengths=(8, 13, 5, 11, 7, 9), n_new=5,
              temps=(0.0, 0.7, 0.0, 1.3, 0.0, 0.7)):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=n_new, temperature=t)
            for i, (n, t) in enumerate(zip(lengths, temps))]


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_batched_matches_serial_reference(arch):
    """Streams bitwise equal, caches numerically equal, per cache family."""
    cfg, model, params = _build(arch)
    streams, caches = {}, {}
    for mode in ("serial", "batched"):
        eng = _engine(model, params, mode)
        for r in _requests(cfg):
            eng.submit(r)
        # admit the first wave only, then snapshot the engine cache: after
        # retirement the stale rows of the two modes legitimately differ
        eng._admit()
        caches[mode] = jax.tree.map(np.asarray, eng.cache)
        m = eng.run()
        assert m.summary()["num_completed"] == 6
        streams[mode] = {r.rid: list(r.tokens) for r in m.completed}
    assert streams["batched"] == streams["serial"]
    for a, b in zip(jax.tree.leaves(caches["batched"]),
                    jax.tree.leaves(caches["serial"])):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=3e-2, atol=3e-2)


def test_admission_order_invariance():
    """(seed, rid)-keyed sampling: the same request yields the same stream
    bitwise no matter the submission order — including sampled rows."""
    cfg, model, params = _build("llama3.2-1b")
    reqs = lambda: _requests(cfg, temps=(1.1, 0.8, 0.0, 1.5, 0.9, 0.0))
    streams = []
    for order in (lambda rs: rs, lambda rs: rs[::-1]):
        eng = _engine(model, params, "batched")
        for r in order(reqs()):
            eng.submit(r)
        m = eng.run()
        streams.append({r.rid: list(r.tokens) for r in m.completed})
    assert streams[0] == streams[1]


def test_burst_admission_dispatch_and_compile_bounds():
    """32 simultaneous submissions: batched admission must spend >= 4x fewer
    compiled model dispatches than the serial reference, and the compile
    caches must stay within the O(log max_seq) x O(log max_batch) budget."""
    cfg, model, params = _build("llama3.2-1b")
    lengths = [5, 9, 13, 17, 21, 25, 29, 30] * 4          # buckets 4/8/16
    calls = {}
    for mode in ("batched", "serial"):
        eng = _engine(model, params, mode, max_batch=8)
        rng = np.random.default_rng(7)
        for i, n in enumerate(lengths):
            eng.submit(Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, size=n).astype(np.int32),
                max_new_tokens=3))
        m = eng.run()
        s = m.summary()
        assert s["num_completed"] == 32
        assert s["prefills"] == 32
        calls[mode] = s["prefill_calls"]
        if mode == "batched":
            n_seq = int(math.log2(eng.max_seq)) + 1
            n_bat = int(math.log2(eng.max_batch)) + 1
            if hasattr(eng._prefill, "_cache_size"):   # private jax API
                assert eng._prefill._cache_size() <= n_seq * n_bat
            if hasattr(eng._extend, "_cache_size"):
                assert eng._extend._cache_size() <= n_seq
            # percentile metrics ride along with the burst regression
            assert s["p99_ttft"] >= s["p50_ttft"] > 0
            assert s["p99_e2e"] >= s["p50_e2e"] >= s["p50_ttft"]
    assert calls["serial"] >= 4 * calls["batched"], calls


def _overlapping_tails(cfg):
    """A request that goes live first, then a wave of three whose tails
    overlap: 15 = 8 + 4+2+1, 14 = 8 + 4+2, 12 = 8 + 4, so the tail chunks
    4, 2 and 1 have three, two and one takers."""
    rng = np.random.default_rng(11)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=new, temperature=t)
            for i, (n, new, t) in enumerate(((8, 12, 0.7), (15, 4, 0.0),
                                             (14, 4, 1.1), (12, 4, 0.0)))]


def _serve_overlapping(eng, cfg):
    first, *wave = _overlapping_tails(cfg)
    eng.submit(first)
    eng.step()
    for r in wave:
        eng.submit(r)
    m = eng.run()
    assert m.summary()["num_completed"] == 4
    return {r.rid: list(r.tokens) for r in m.completed}


@pytest.mark.parametrize("mode", ["batched", "async"])
def test_overlapping_tails_extend_only_their_rows(mode):
    """The paged extend runs the pow-2 cover of the rows that take each
    chunk (three takers -> 4 rows, one of them padding; then 2; then 1),
    each taker's logits come back at its compacted row, and the streams
    stay bitwise those of the serial reference and the dense cache."""
    cfg, model, params = _build("qwen3-14b")
    eng = _engine(model, params, mode, paged=True)
    seen = []
    inner = eng._dispatch_extend

    def spy(chunks, C):
        logits, at = inner(chunks, C)
        seen.append((len(chunks), logits.shape[0], list(at)))
        return logits, at

    eng._dispatch_extend = spy
    got = _serve_overlapping(eng, cfg)
    assert seen == [(3, 4, [0, 1, 2]), (2, 2, [0, 1]), (1, 1, [0])]
    assert got == _serve_overlapping(_engine(model, params, "serial"), cfg)
    assert got == _serve_overlapping(_engine(model, params, mode), cfg)
    assert eng.reconcile()["balanced"]


def test_admit_token_budget_bounds_per_step_work():
    """The budget caps prompt tokens admitted per step (FIFO, >= 1 request
    per step so oversized prompts cannot starve), trading admission
    throughput for bounded TBT inflation of live slots."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, "batched", max_batch=8,
                  admit_token_budget=16)
    rng = np.random.default_rng(3)
    for i in range(8):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=8).astype(np.int32), max_new_tokens=8))
    live = eng.step()
    assert live == 2                       # 16-token budget -> 2 prompts
    assert len(eng.waiting) == 6
    m = eng.run()
    assert m.summary()["num_completed"] == 8


def test_oversized_requests_rejected_queue_keeps_draining():
    """Prompts that can never fit (prompt + decode tail > max_seq) are
    rejected without consuming a slot; the queue keeps serving."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, "batched", max_seq=32)
    rng = np.random.default_rng(4)
    eng.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab_size, size=40)
                       .astype(np.int32), max_new_tokens=2))     # prompt > cache
    eng.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab_size, size=8)
                       .astype(np.int32), max_new_tokens=30))    # tail > cache
    eng.submit(Request(rid=2, prompt=np.zeros(0, np.int32), max_new_tokens=2))
    eng.submit(Request(rid=3, prompt=rng.integers(0, cfg.vocab_size, size=8)
                       .astype(np.int32), max_new_tokens=4))     # fits
    m = eng.run()
    s = m.summary()
    assert s["rejected"] == 3 and s["num_completed"] == 1
    assert {r.rid for r in m.rejected} == {0, 1, 2}
    assert m.completed[0].rid == 3


def test_single_token_request_completes_at_admission():
    """max_new_tokens=1: the prompt's last logits give the only requested
    token; the slot never goes live and no extra decode token is emitted
    (regression: the serial engine appended a second, unrequested token)."""
    cfg, model, params = _build("llama3.2-1b")
    for mode in ("serial", "batched"):
        eng = _engine(model, params, mode)
        rng = np.random.default_rng(6)
        eng.submit(Request(rid=0, prompt=rng.integers(
            0, cfg.vocab_size, size=9).astype(np.int32), max_new_tokens=1))
        eng.submit(Request(rid=1, prompt=rng.integers(          # degenerate:
            0, cfg.vocab_size, size=9).astype(np.int32),        # 0 requested
            max_new_tokens=0))                                  # -> 0 emitted
        m = eng.run()
        assert m.summary()["num_completed"] == 2
        got = {r.rid: len(r.tokens) for r in m.completed}
        assert got == {0: 1, 1: 0}
        assert all(r is None for r in eng.active)


def test_vlm_prefix_counts_against_cache_capacity():
    """The oversize-rejection guard must account for the VLM patch prefix,
    which occupies decode-cache rows (regression: prefix+prompt+tail
    overflowed max_seq and was silently dropped by OOB scatter)."""
    cfg, model, params = _build("paligemma-3b")
    prefix = cfg.num_prefix_embeddings
    eng = _engine(model, params, "batched", max_seq=16)
    rng = np.random.default_rng(8)
    eng.submit(Request(rid=0, prompt=rng.integers(                 # 8+12+3 > 16
        0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=4))
    eng.submit(Request(rid=1, prompt=rng.integers(                 # 8+5+2 <= 16
        0, cfg.vocab_size, size=16 - prefix - 3).astype(np.int32),
        max_new_tokens=3))
    m = eng.run()
    s = m.summary()
    assert s["rejected"] == 1 and m.rejected[0].rid == 0
    assert s["num_completed"] == 1 and m.completed[0].rid == 1


def test_release_slot_on_admission_error(monkeypatch):
    """An exception mid-admission releases the claimed slots (release_slot),
    records the failing request as rejected, and requeues its round-mates
    — accounting stays reconciled and the engine stays serviceable."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, "batched")
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(2)]

    def boom(*a, **k):
        raise RuntimeError("injected insert failure")

    monkeypatch.setattr(engine_mod, "insert_cache_rows", boom)
    eng.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=3))
    eng.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=3))
    with pytest.raises(RuntimeError, match="injected"):
        eng.step()
    assert all(r is None for r in eng.active)
    # round-mates went back to the queue, not into the void
    assert [r.rid for r in eng.waiting] == [0, 1]
    assert not eng.metrics.rejected            # insert failed pre-finalize
    monkeypatch.undo()
    m = eng.run()
    assert m.summary()["num_completed"] == 2


# ------------------------------------------------- lifecycle (ISSUE 6)
def test_release_slot_error_paths():
    """release_slot is idempotent for free/never-admitted slots and
    raises on out-of-range ids; the engine stays serviceable."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, "batched")
    eng.release_slot(2)                      # never admitted: no-op
    rng = np.random.default_rng(4)
    eng.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=6).astype(np.int32), max_new_tokens=2))
    eng.run()
    eng.release_slot(0)                      # already retired on finish
    eng.release_slot(0)                      # double release: no-op
    with pytest.raises(ValueError):
        eng.release_slot(eng.max_batch)
    with pytest.raises(ValueError):
        eng.release_slot(-1)
    eng.submit(Request(rid=1, prompt=rng.integers(
        0, cfg.vocab_size, size=6).astype(np.int32), max_new_tokens=2))
    m = eng.run()
    assert m.summary()["num_completed"] == 2
    assert eng.reconcile()["balanced"]


def test_reject_then_resubmit_same_rid():
    """An oversize rejection leaves no residue keyed on the rid: the
    same rid resubmitted at a legal size admits and completes."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, "batched", max_seq=16)
    rng = np.random.default_rng(4)
    eng.submit(Request(rid=7, prompt=rng.integers(        # 14 + 8 - 1 > 16
        0, cfg.vocab_size, size=14).astype(np.int32), max_new_tokens=8))
    m = eng.run()
    assert [r.rid for r in m.rejected] == [7]
    eng.submit(Request(rid=7, prompt=rng.integers(        # 6 + 3 - 1 <= 16
        0, cfg.vocab_size, size=6).astype(np.int32), max_new_tokens=3))
    m = eng.run()
    assert [r.rid for r in m.completed] == [7]
    assert len(m.completed[0].tokens) == 3
    assert eng.reconcile()["balanced"]


def test_deadline_timeout_queued_and_active():
    """Absolute deadlines: a queued request past its deadline is swept
    before burning prefill; an in-flight one is evicted mid-decode and
    its generated tokens count as lost."""
    cfg, model, params = _build("llama3.2-1b")
    clk = [0.0]
    eng = _engine(model, params, "batched", clock=lambda: clk[0])
    rng = np.random.default_rng(4)
    mk = lambda rid, dl: Request(
        rid=rid, prompt=rng.integers(0, cfg.vocab_size, size=6)
        .astype(np.int32), max_new_tokens=20, deadline_s=dl)
    eng.submit(mk(0, 1.0))                   # dead before admission
    eng.submit(mk(1, 50.0))                  # dies mid-decode
    clk[0] = 2.0
    eng.step()
    assert [r.rid for r in eng.metrics.timed_out] == [0]
    assert eng.active[0] is not None and eng.active[0].rid == 1
    eng.step()                               # a couple of live tokens
    clk[0] = 60.0
    eng.step()
    assert [r.rid for r in eng.metrics.timed_out] == [0, 1]
    assert eng.metrics.lost_tokens >= 2      # rid 1's generated tokens
    assert all(r is None for r in eng.active)
    assert eng.reconcile()["balanced"]


def test_backoff_hold_does_not_starve_queue():
    """A backoff-gated request (not_before_s in the future) holds its
    queue position without blocking requests behind it."""
    cfg, model, params = _build("llama3.2-1b")
    clk = [0.0]
    eng = _engine(model, params, "batched", clock=lambda: clk[0])
    rng = np.random.default_rng(4)
    held = Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=6).astype(np.int32), max_new_tokens=2,
        not_before_s=10.0)
    eng.submit(held)
    eng.submit(Request(rid=1, prompt=rng.integers(
        0, cfg.vocab_size, size=6).astype(np.int32), max_new_tokens=2))
    eng.step()                               # rid 1 jumps the gate
    assert any(r is not None and r.rid == 1 for r in eng.active) \
        or any(r.rid == 1 for r in eng.metrics.completed)
    assert [r.rid for r in eng.waiting] == [0]
    clk[0] = 10.0                            # gate opens (now >= not_before)
    m = eng.run()
    assert sorted(r.rid for r in m.completed) == [0, 1]
    assert eng.reconcile()["balanced"]


def test_queue_watermark_backpressure():
    """Past the watermark, submit() fails fast and records the
    rejection instead of letting the queue grow unboundedly."""
    cfg, model, params = _build("llama3.2-1b")
    eng = _engine(model, params, "batched", queue_watermark=2)
    rng = np.random.default_rng(4)
    oks = [eng.submit(Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=6).astype(np.int32), max_new_tokens=2))
        for i in range(4)]
    assert oks == [True, True, False, False]
    m = eng.run()
    assert sorted(r.rid for r in m.completed) == [0, 1]
    assert sorted(r.rid for r in m.rejected) == [2, 3]
    assert eng.reconcile()["balanced"]


def test_brownout_sheds_fresh_requests_only():
    """Brownout sheds a fresh request's max_new_tokens to
    ceil(frac * requested); resumed transcripts keep their contract
    (shedding them would break the bit-identity anchor)."""
    cfg, model, params = _build("llama3.2-1b")
    rng = np.random.default_rng(4)
    src = _engine(model, params, "batched", seed=0)
    src.submit(Request(rid=5, prompt=rng.integers(
        0, cfg.vocab_size, size=6).astype(np.int32), max_new_tokens=8,
        temperature=0.9))
    for _ in range(3):
        src.step()
    snap, = src.preempt()
    assert len(snap.tokens) == 4             # 1 at admission + 3 decode steps

    eng = _engine(model, params, "batched", seed=1)
    eng.set_brownout(0.5)
    assert eng.brownout == 0.5
    eng.submit(Request(rid=9, prompt=rng.integers(
        0, cfg.vocab_size, size=6).astype(np.int32), max_new_tokens=8))
    assert eng.resume(snap) is not None
    m = eng.run()
    got = {r.rid: len(r.tokens) for r in m.completed}
    assert got == {9: 4, 5: 8}               # fresh shed, resumed intact
    assert eng.metrics.shed_tokens == 4
    eng.set_brownout(1.5)                    # clamped
    assert eng.brownout == 1.0
    eng.set_brownout(-0.5)
    assert eng.brownout == 0.0
