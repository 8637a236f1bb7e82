"""The serving engine's own spans in a profiler trace, and what they name.

The engine writes host spans named ``engine.*`` (``step`` and, nested in
it, ``admit``, ``prefill``, ``extend``, ``first_token``, ``decode``,
``sample``, ``retire``) with integer arguments: the rows each dispatch
serves (``rows``) and the rows its program computes (``batch``). A run's
extract (``bench.trace.extract``) keeps only the harness's ``bench.*``
spans; ``extract`` here adds the program's under ``program``, as
``[name, start_ns, duration_ns, {argument: value}]`` on the trace's one
clock, and the functions below read them:

- ``top_gaps`` names each idle gap of the device by the innermost span,
  the harness's or the program's, open at its middle, and
  ``idle_by_span`` sums every gap by that name;
- ``step_idle_ms``: device idle time inside ``engine.step`` spans per step
  (host time of the engine's step that the device waits on);
- ``row_share``: Σ ``rows`` / Σ ``batch`` over ``engine.extend`` or
  ``engine.decode`` spans, the share of the rows computed that serve a
  request.

Until ``bench.trace.extract`` keeps ``program_spans`` itself, a traced
run keeps the program's spans only where ``extract`` here stands in for
it, as the tests make it do.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Optional

from bench import trace as T

PREFIX = "engine."
STEP = "engine.step"
_bench_extract = T.extract       # held: ``extract`` may stand in for it


def program_spans(trace_dir: str) -> list:
    """The ``engine.*`` host events of the newest ``.xplane.pb`` under
    ``trace_dir``, with their arguments, sorted by start."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend([e.name, e.start_ns, e.duration_ns,
                            {k: int(v) for k, v in e.stats}]
                           for e in line.events if e.name.startswith(PREFIX))
    return sorted(out, key=lambda s: s[1])


def extract(trace_dir: str) -> dict:
    """``bench.trace.extract`` with the program's spans under ``program``."""
    tr = _bench_extract(trace_dir)
    tr["program"] = program_spans(trace_dir)
    return tr


def _spans(tr: dict) -> list:
    return tr["host"] + [s[:3] for s in tr.get("program", [])]


def top_gaps(tr: dict, dev: dict, lo, hi, n: int = 10):
    """``bench.trace.top_gaps`` with the program's spans among the names:
    a gap inside the engine's step is named by the engine's span."""
    return T.top_gaps({**tr, "host": _spans(tr)}, dev, lo, hi, n)


def idle_by_span(tr: dict, dev: dict, lo, hi) -> list:
    """Every idle gap of ``[lo, hi]``, summed by the span open at its
    middle, as ``[name, seconds, gaps]``, longest first."""
    spans = _spans(tr)
    tot: dict = defaultdict(lambda: [0.0, 0])
    for a, b in T.idle_gaps(dev["ops"], lo, hi):
        t = tot[T.span_at(spans, (a + b) / 2)]
        t[0] += (b - a) / 1e9
        t[1] += 1
    return sorted(([k, s, c] for k, (s, c) in tot.items()),
                  key=lambda x: -x[1])


def _overlap_ns(xs, ys) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def step_idle_ms(tr: dict, dev: dict, lo, hi) -> Optional[float]:
    """Device idle time within ``[lo, hi]`` inside ``engine.step`` spans,
    divided by the ``engine.step`` spans that start in it."""
    steps = [s for s in tr.get("program", []) if s[0] == STEP]
    n = sum(lo <= s[1] < hi for s in steps)
    if not n:
        return None
    idle = _overlap_ns(T.idle_gaps(dev["ops"], lo, hi),
                       [(s[1], s[1] + s[2]) for s in steps])
    return idle / 1e6 / n


def row_share(tr: dict, name: str, lo, hi) -> Optional[float]:
    """Σ ``rows`` / Σ ``batch`` over the ``name`` spans starting in
    ``[lo, hi]``."""
    spans = [s for s in tr.get("program", [])
             if s[0] == name and lo <= s[1] < hi]
    batch = sum(s[3]["batch"] for s in spans)
    return sum(s[3]["rows"] for s in spans) / batch if batch else None


def trim(tr: dict, lo: float, hi: float) -> dict:
    """``bench.trace.trim`` that keeps the program's spans of the part."""
    out = T.trim(tr, lo, hi)
    out["program"] = [s for s in tr.get("program", []) if lo <= s[1] < hi]
    return out
