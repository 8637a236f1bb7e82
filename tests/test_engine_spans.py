"""The serving engine's host spans and admission stamps.

Under a profiler session every ``engine.*`` span nests in the step that
ran it and carries the rows its dispatch serves and computes; the engine's
programs carry names a trace reduction can find. ``Request.admit_s``
orders arrival, admission and first token in every admit mode, a
preempted request keeps it through its snapshot, and a failed admission
round takes back only the stamps it set.
"""
from __future__ import annotations

import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.checkpoint.store import TranscriptSnapshot
from repro.configs import smoke_config
from repro.models.api import build
from repro.serving.engine import Request, ServingEngine

_BUILT: dict = {}


def _model():
    if not _BUILT:
        cfg = smoke_config("qwen3-14b")
        model = build(cfg)
        _BUILT["m"] = (cfg, model, model.init_params(jax.random.key(0)))
    return _BUILT["m"]


def _request(cfg, rid, n, new, rng):
    return Request(rid=rid, max_new_tokens=new, arrival_s=time.perf_counter(),
                   prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32))


def _read_spans(trace_dir):
    """``engine.*`` host events as (name, start_ns, end_ns, args)."""
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("engine."))
    return sorted(out, key=lambda s: s[1])


def test_spans_nest_in_steps_and_count_rows(tmp_path):
    cfg, model, params = _model()
    eng = ServingEngine(model, params, max_batch=4, max_seq=128,
                        admit_mode="async", admit_token_budget=16,
                        paged=True, page_size=8)
    rng = np.random.default_rng(0)
    # 37 = bucket 32 + a tail of 5 (extend chunks 4 and 1); 20 = 16 + 4
    reqs = [_request(cfg, 0, 37, 6, rng), _request(cfg, 1, 20, 4, rng)]
    for r in reqs:
        assert eng.submit(r)
    served = []                          # decode rows of each step, seen
    jax.profiler.start_trace(str(tmp_path))
    try:
        while eng.waiting or eng.reconcile()["active"]:
            before = [len(r.tokens) for r in reqs]
            eng.step()
            served.append(sum(max(0, len(r.tokens) - max(n0, 1))
                              for r, n0 in zip(reqs, before)))
    finally:
        jax.profiler.stop_trace()
    spans = _read_spans(str(tmp_path))
    names = {s[0] for s in spans}
    assert {"engine.step", "engine.admit", "engine.prefill",
            "engine.extend", "engine.first_token", "engine.decode",
            "engine.sample", "engine.retire"} <= names
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == len(served)
    for name, a, b, _ in spans:
        if name in ("engine.admit", "engine.decode", "engine.sample",
                    "engine.retire", "engine.extend", "engine.prefill"):
            assert any(sa <= a and b <= sb for _, sa, sb, _ in steps), name
    extends = [s[3] for s in spans if s[0] == "engine.extend"]
    assert extends
    for x in extends:
        # the paged extend runs the pow-2 cover of the rows that take it
        assert x["batch"] == 1 << (x["rows"] - 1).bit_length()
        assert x["chunk"] & (x["chunk"] - 1) == 0
    decodes = [s[3] for s in spans if s[0] == "engine.decode"]
    assert [d["rows"] for d in decodes] == [n for n in served if n]
    assert all(d["batch"] == eng.max_batch and d["pages"] >= 1
               for d in decodes)
    admitted = sum(s[3]["admitted"] for s in spans if s[0] == "engine.admit")
    assert admitted == len(reqs)
    finished = sum(s[3]["finished"] for s in spans if s[0] == "engine.retire")
    assert finished == len(reqs)


@pytest.mark.parametrize("mode", ["serial", "batched", "async"])
def test_admit_stamp_lies_between_arrival_and_first_token(mode):
    cfg, model, params = _model()
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        admit_mode=mode, admit_token_budget=24)
    rng = np.random.default_rng(1)
    reqs = [_request(cfg, i, n, 3, rng) for i, n in enumerate((9, 16, 5))]
    for r in reqs:
        assert eng.submit(r)
    eng.run()
    assert len(eng.metrics.completed) == len(reqs)
    for r in reqs:
        assert r.arrival_s <= r.admit_s <= r.prefill_done_s <= r.finish_s
    # max_batch 2: the third request waits for a slot, and its stamp shows
    assert reqs[2].admit_s > reqs[0].admit_s


def test_admit_stamp_survives_preempt_and_resume():
    cfg, model, params = _model()
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        admit_mode="batched")
    rng = np.random.default_rng(2)
    reqs = [_request(cfg, i, n, 8, rng) for i, n in enumerate((9, 12, 6))]
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()
    snaps = [TranscriptSnapshot.from_json(s.to_json()) for s in eng.drain()]
    by_rid = {s.rid: s for s in snaps}
    for r in reqs[:2]:                     # admitted, mid-stream
        assert 0 < len(by_rid[r.rid].tokens) < 8
        assert by_rid[r.rid].admit_s == r.admit_s is not None
    assert by_rid[2].admit_s is None       # still queued when drained
    eng2 = ServingEngine(model, params, max_batch=2, max_seq=64,
                         admit_mode="batched", seed=5)
    resumed = [eng2.resume(s) for s in snaps]
    eng2.run()
    for r, q in zip(reqs[:2], resumed[:2]):
        assert q.admit_s == r.admit_s      # the first admission, kept
        assert q.prefill_done_s == r.prefill_done_s
    assert resumed[2].arrival_s <= resumed[2].admit_s \
        <= resumed[2].prefill_done_s


@pytest.mark.parametrize("mode", ["batched", "async"])
def test_failed_admission_round_unstamps_its_requests(mode, monkeypatch):
    """A round whose insert fails sends its requests back to the queue:
    the one it first admitted loses its stamp and is stamped again when it
    is admitted for good; a resumed one keeps the stamp it carried."""
    from repro.serving import engine as engine_mod

    cfg, model, params = _model()
    rng = np.random.default_rng(3)
    first = ServingEngine(model, params, max_batch=1, max_seq=64,
                          admit_mode="batched")
    first.submit(_request(cfg, 1, 12, 6, rng))
    first.step()
    snap, = first.drain()
    assert snap.admit_s is not None and snap.tokens
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        admit_mode=mode)
    fresh = _request(cfg, 0, 9, 3, rng)
    assert eng.submit(fresh)
    resumed = eng.resume(snap)

    def boom(*a, **k):
        raise RuntimeError("injected insert failure")

    monkeypatch.setattr(engine_mod, "insert_cache_rows", boom)
    with pytest.raises(RuntimeError, match="injected"):
        eng.step()
    assert [r.rid for r in eng.waiting] == [0, 1]
    assert fresh.admit_s is None
    assert resumed.admit_s == snap.admit_s
    monkeypatch.undo()
    retry = time.perf_counter()
    eng.run()
    assert len(eng.metrics.completed) == 2
    assert retry <= fresh.admit_s <= fresh.prefill_done_s
    assert resumed.admit_s == snap.admit_s


def test_engine_programs_have_names():
    """XLA names a program after its jitted function; the prefill's name
    holds ``prefill`` and no other engine program's does."""
    cfg, model, params = _model()
    eng = ServingEngine(model, params, max_batch=2, max_seq=64,
                        admit_mode="async", paged=True, page_size=8)
    names = {k: getattr(eng, k).__name__ for k in
             ("_prefill", "_decode", "_extend", "_extend_paged",
              "_decode_masked")}
    tokens = jax.ShapeDtypeStruct((1, 8), np.int32)
    text = eng._prefill.lower(params, {"tokens": tokens}).as_text()
    assert f"@jit_{names['_prefill']}" in text.splitlines()[0]
    assert "prefill" in names["_prefill"]
    for k, n in names.items():
        assert "unknown" not in n and "<" not in n
        if k != "_prefill":
            assert "prefill" not in n
        if k in ("_prefill", "_extend", "_extend_paged"):
            assert "decode" not in n
        if k != "_prefill":
            assert ("extend" in n) == k.startswith("_extend")
