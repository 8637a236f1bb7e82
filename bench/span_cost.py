"""Host cost of the serving engine's spans with no profiler session running.

Enters and leaves, through the engine's own span helper, the spans one
engine step opens: a decode step (``step``, ``admit``, ``decode``,
``sample``, ``retire``) and an admission step that also prefills, extends
a tail and samples first tokens. Prints one JSON line: microseconds per
step of each kind, five repeats of 20,000 steps each.

    PYTHONPATH=src python3 bench/span_cost.py
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.serving.engine import _host_span as span  # noqa: E402

STEPS = 20_000


def decode_step():
    with span("step", live=2, waiting=0, pending=0):
        admit = span("admit")
        with admit:
            admit.set_metadata(admitted=0, tokens=0)
        with span("decode", rows=2, batch=16, pages=12):
            pass
        with span("sample"):
            pass
        retire = span("retire")
        with retire:
            retire.set_metadata(finished=0)


def admit_step():
    with span("step", live=1, waiting=1, pending=0):
        admit = span("admit")
        with admit:
            with span("prefill", bucket=512, rows=1, batch=1):
                pass
            with span("first_token", rows=1):
                pass
            with span("extend", chunk=128, rows=1, batch=16):
                pass
            admit.set_metadata(admitted=1, tokens=640)
        with span("decode", rows=2, batch=16, pages=12):
            pass
        with span("sample"):
            pass
        retire = span("retire")
        with retire:
            retire.set_metadata(finished=0)


def per_step_us(fn) -> list:
    out = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            fn()
        out.append((time.perf_counter() - t0) / STEPS * 1e6)
    return out


if __name__ == "__main__":
    print(json.dumps({"decode_step_us": per_step_us(decode_step),
                      "admit_step_us": per_step_us(admit_step)}))
