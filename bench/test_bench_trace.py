"""The reduction from a profiler trace to per-layer metrics: exact on
hand-made events, and consistent on a short trace recorded on a TPU v5e
running the qwen3-14b.conversation cell (``testdata/trace_qwen.json``)."""
import json
import os

from bench import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "testdata", "trace_qwen.json")

# window 0..100 ns; ops overlap at 10..30 and 25..40, one op straddles 90
HAND = {
    "devices": [{"name": "/device:TPU:0",
                 "ops": [["fusion.1", 10, 20], ["fusion.2", 25, 15],
                         ["dot.3", 60, 10], ["fusion.1", 90, 30]],
                 "modules": [["jit_prefill_fn", 10, 30],
                             ["jit__masked_decode", 60, 10],
                             ["jit__masked_extend_paged", 90, 30],
                             ["jit__masked_decode", 150, 5]]}],
    "host": [["bench.window", 0, 100], ["bench.step", 5, 40],
             ["bench.idle", 45, 10], ["bench.record", 70, 15]],
}


def test_busy_and_idle_by_hand():
    ops = HAND["devices"][0]["ops"]
    assert T.window(HAND) == (0.0, 100.0)
    assert T.busy_intervals(ops, 0, 100) == [(10, 40), (60, 70), (90, 100)]
    assert T.busy_ns(ops, 0, 100) == 50
    assert T.idle_gaps(ops, 0, 100) == [(0, 10), (40, 60), (70, 90)]


def test_gaps_are_named_by_the_open_span():
    dev = HAND["devices"][0]
    gaps = T.top_gaps(HAND, dev, 0, 100, n=3)
    assert [g[0] for g in gaps] == ["bench.idle", "bench.record",
                                    "bench.step"]
    assert [g[1] for g in gaps] == [20e-9, 20e-9, 10e-9]


def test_ops_and_modules_by_hand():
    dev = HAND["devices"][0]
    assert T.top_ops(dev, 0, 100) == [["fusion.1", 30e-9],
                                      ["fusion.2", 15e-9], ["dot.3", 10e-9]]
    assert T.module_time(dev, 0, 100, "decode") == (10e-9, 1)
    s, n = T.module_time(dev, 0, 100, "extend")
    assert (s, n) == (30e-9, 1)


def test_trim_keeps_the_window():
    small = T.trim(HAND, 50, 100)
    assert [o[0] for o in small["devices"][0]["ops"]] == ["dot.3",
                                                         "fusion.1"]
    assert T.window(small) == (0.0, 100.0)


def test_recorded_trace_is_consistent():
    with open(RECORDED) as f:
        tr = json.load(f)
    lo, _ = T.window(tr)
    dev = tr["devices"][0]
    hi = max(st + du for _, st, du in dev["ops"])
    busy = T.busy_ns(dev["ops"], lo, hi)
    idle = sum(b - a for a, b in T.idle_gaps(dev["ops"], lo, hi))
    assert busy > 0 and abs(busy + idle - (hi - lo)) < 1e-3 * (hi - lo)
    # the engine's programs are found by name
    names = {m[0] for m in dev["modules"]}
    assert any("decode" in n for n in names)
    s, n = T.module_time(dev, lo, hi, "decode")
    assert n > 0 and 0 < s * 1e9 <= busy * 1.01
    ops_total = sum(v for _, v in T.top_ops(dev, lo, hi, n=10 ** 6))
    assert ops_total * 1e9 >= busy
