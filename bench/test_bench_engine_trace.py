"""The program's spans in a trace extract (``bench/engine_trace.py``) and
the engine's admission stamps read by ``admit_wait_mean_ms`` and
``admit_service_mean_ms``: exact on hand-made events, consistent on a
traced CPU run of the tiny cell, and on a short trace recorded on a TPU v5e
running the qwen3-14b.conversation cell (``testdata/trace_engine.json``)."""
import json
import os

import pytest

from bench import engine_trace as E
from bench import harness as H
from bench import trace as T
from bench.test_bench_trace import HAND

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "testdata", "trace_engine.json")
SEED = 2 ** 31 + 4343

# window 0..200 ns; idle gaps (0,10), (30,50), (60,120), (150,200)
PROG = {
    "devices": [{"name": "/device:TPU:0",
                 "ops": [["fusion.1", 10, 20], ["fusion.2", 50, 10],
                         ["fusion.3", 120, 30]],
                 "modules": []}],
    "host": [["bench.window", 0, 200], ["bench.step", 5, 95],
             ["bench.idle", 100, 10], ["bench.step", 110, 80]],
    "program": [
        ["engine.step", 6, 93, {"live": 1, "waiting": 1, "pending": 0}],
        ["engine.admit", 7, 38, {"admitted": 1, "tokens": 4}],
        ["engine.extend", 8, 27, {"chunk": 4, "rows": 1, "batch": 4}],
        ["engine.decode", 45, 17, {"rows": 1, "batch": 4, "pages": 2}],
        ["engine.sample", 62, 8, {}],
        ["engine.retire", 70, 28, {"finished": 0}],
        ["engine.step", 111, 78, {"live": 2, "waiting": 0, "pending": 0}],
        ["engine.decode", 112, 38, {"rows": 2, "batch": 4, "pages": 2}],
        ["engine.sample", 150, 10, {}],
        ["engine.retire", 160, 28, {"finished": 1}]],
}


def test_program_spans_read_by_hand():
    dev = PROG["devices"][0]
    # idle inside the steps: 4 + 20 + 39 in the first, 9 + 39 in the second
    assert E.step_idle_ms(PROG, dev, 0, 200) == pytest.approx(111 / 2 / 1e6)
    assert E.row_share(PROG, "engine.extend", 0, 200) == 0.25
    assert E.row_share(PROG, "engine.decode", 0, 200) == 3 / 8
    assert E.row_share(PROG, "engine.decode", 100, 200) == 0.5
    assert E.step_idle_ms(PROG, dev, 150, 200) is None   # no step starts
    gaps = E.top_gaps(PROG, dev, 0, 200)
    assert gaps == [["engine.retire", 60e-9], ["engine.retire", 50e-9],
                    ["engine.admit", 20e-9], ["bench.step", 10e-9]]
    # the harness's spans alone name every one of them bench.step
    assert [g[0] for g in T.top_gaps(PROG, dev, 0, 200)] == ["bench.step"] * 4
    by = {k: (s * 1e9, n) for k, s, n in E.idle_by_span(PROG, dev, 0, 200)}
    assert by == {"engine.retire": (pytest.approx(110), 2),
                  "engine.admit": (pytest.approx(20), 1),
                  "bench.step": (pytest.approx(10), 1)}
    small = E.trim(PROG, 100, 200)
    assert [s[0] for s in small["program"]] == [
        "engine.step", "engine.decode", "engine.sample", "engine.retire"]
    assert T.window(small) == (0.0, 200.0)


def test_an_extract_without_program_spans_reads_as_before():
    dev = HAND["devices"][0]
    assert E.top_gaps(HAND, dev, 0, 100) == T.top_gaps(HAND, dev, 0, 100)
    assert E.step_idle_ms(HAND, dev, 0, 100) is None
    assert E.row_share(HAND, "engine.decode", 0, 100) is None


def test_traced_run_stamps_admission_and_keeps_program_spans(
        tiny_root, monkeypatch):
    monkeypatch.setattr(T, "extract", E.extract)   # keep the engine's spans
    out = H.run_cell(tiny_root, "tiny.tiny", SEED, 1.0, True, check=False,
                     require_accelerator=False)
    data = out["_data"]
    recs = data.window_recs()
    assert recs and all(r.req.admit_s is not None for r in recs)
    step_end = {s.t0: s.t1 for s in data.steps}
    for r in recs:
        assert r.due <= r.req.arrival_s <= r.req.admit_s
        assert r.left_queue <= r.req.admit_s <= step_end[r.left_queue]
        assert r.req.admit_s <= r.stamps[0]
    wait = H.load_reader(tiny_root, "admit_wait_mean_ms")(data)
    service = H.load_reader(tiny_root, "admit_service_mean_ms")(data)
    ttft = sum(data.ttft_s(r) for r in recs) / len(recs) * 1e3
    assert wait > 0 and service > 0
    assert abs(wait + service - ttft) < 1e-6
    queue = H.load_reader(tiny_root, "queue_wait_mean_ms")(data)
    assert queue <= wait                    # the step starts before it admits
    names = {s[0] for s in data.trace["program"]}
    assert {"engine.step", "engine.admit", "engine.decode",
            "engine.sample", "engine.retire"} <= names
    lo, hi = T.window(data.trace)
    steps = sum(s[0] == E.STEP and lo <= s[1] < hi
                for s in data.trace["program"])
    assert steps == sum(data.win[0] <= s.t0 < data.win[1]
                        for s in data.steps)
    assert 0 < E.row_share(data.trace, "engine.decode", lo, hi) <= 1


def test_admission_readers_give_none_without_the_stamp():
    class Req:                               # a program that stamps nothing
        pass

    class Rec:
        req, due, stamps = Req(), 1.0, [2.0]

    run = type("Run", (), {"window_recs": lambda self: [Rec()],
                           "drain_end": 3.0})()
    for name in ("admit_wait_mean_ms", "admit_service_mean_ms"):
        assert H.load_reader(H.ROOT, name)(run) is None


def test_recorded_engine_trace_names_every_gap_in_a_step():
    with open(RECORDED) as f:
        tr = json.load(f)
    steps = [s for s in tr["program"] if s[0] == E.STEP]
    dev = tr["devices"][0]
    # the recorded part keeps the events that start in it: no op is kept
    # past its end, and the last step may run on beyond
    lo = steps[0][1]
    hi = min(steps[-1][1] + steps[-1][2],
             max(st + du for _, st, du in dev["ops"]))
    spans = E._spans(tr)
    inside = 0
    for a, b in T.idle_gaps(dev["ops"], lo, hi):
        mid = (a + b) / 2
        if any(st <= mid < st + du for _, st, du, _ in steps):
            inside += 1
            assert T.span_at(spans, mid).startswith("engine."), (a, b)
    assert inside > 0
    assert E.step_idle_ms(tr, dev, lo, hi) > 0
    assert 0 < E.row_share(tr, "engine.extend", lo, hi) <= 1
    assert 0 < E.row_share(tr, "engine.decode", lo, hi) <= 1
    # the engine's programs have names the reduction finds
    mods = {m[0].split("(")[0] for m in dev["modules"]}
    assert not any("_unknown" in m for m in mods)
    assert any("prefill" in m for m in mods) or any(
        "extend" in m for m in mods)
    assert not any("prefill" in m and ("extend" in m or "decode" in m)
                   for m in mods)
