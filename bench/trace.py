"""Reduction of a profiler trace to the events the metrics read.

``extract`` turns an ``.xplane.pb`` into a small dict: per device, its XLA
op and XLA module events, and the harness's own host spans (names start
with ``bench.``), all as ``[name, start_ns, duration_ns]`` on the trace's
one clock. The functions below it compute from that dict alone, so they
are checked on a small recorded extract without a chip.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def extract(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": [], "host": [], "planes": []}
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        out["planes"].append([plane.name, sorted(lines)])
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            ev = lambda name: [[e.name, e.start_ns, e.duration_ns]
                               for e in lines[name].events] \
                if name in lines else []
            out["devices"].append({"name": plane.name,
                                   "ops": ev("XLA Ops"),
                                   "modules": ev("XLA Modules")})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns, e.duration_ns]
                                   for e in line.events
                                   if e.name.startswith(SPAN_PREFIX))
    return out


def window(tr: dict) -> tuple[float, float]:
    """(start_ns, end_ns) of the traced window, from its host span."""
    spans = [s for s in tr["host"] if s[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace has no bench.window span")
    s = spans[0]
    return float(s[1]), float(s[1] + s[2])


def _clip(events, lo, hi):
    for name, st, du in events:
        a, b = max(st, lo), min(st + du, hi)
        if b > a:
            yield name, a, b


def busy_intervals(events, lo, hi) -> list[tuple[float, float]]:
    """Union of the events' intervals within ``[lo, hi]``, sorted."""
    iv = sorted((a, b) for _, a, b in _clip(events, lo, hi))
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events, lo, hi) -> float:
    return sum(b - a for a, b in busy_intervals(events, lo, hi))


def idle_gaps(events, lo, hi) -> list[tuple[float, float]]:
    """Intervals of ``[lo, hi]`` in which no event runs."""
    gaps, t = [], lo
    for a, b in busy_intervals(events, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_at(host, t: float) -> str:
    """Name of the innermost harness span open at ``t`` (the window span
    itself does not count)."""
    best, best_len = "outside_spans", float("inf")
    for name, st, du in host:
        if name != WINDOW_SPAN and st <= t < st + du and du < best_len:
            best, best_len = name, du
    return best


def top_gaps(tr: dict, dev: dict, lo, hi, n: int = 10):
    """The ``n`` longest idle gaps as ``[span open at its middle, s]``."""
    gaps = sorted(idle_gaps(dev["ops"], lo, hi), key=lambda g: g[0] - g[1])
    return [[span_at(tr["host"], (a + b) / 2), (b - a) / 1e9]
            for a, b in gaps[:n]]


def top_ops(dev: dict, lo, hi, n: int = 10, width: int = 120):
    """Device time by op, longest first, as ``[name, s]``; an op's name is
    its HLO text, cut to ``width`` characters (name, result shape, kind)."""
    tot: dict[str, float] = defaultdict(float)
    for name, a, b in _clip(dev["ops"], lo, hi):
        tot[name] += b - a
    return [[k[:width], v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def module_time(dev: dict, lo, hi, pattern: str) -> tuple[float, int]:
    """(seconds, calls) of the XLA modules whose name holds ``pattern``
    and which start inside the window."""
    tot, calls = 0.0, 0
    for name, st, du in dev["modules"]:
        if pattern in name and lo <= st < hi:
            tot += du
            calls += 1
    return tot / 1e9, calls


def trim(tr: dict, lo: float, hi: float) -> dict:
    """The part of an extract inside ``[lo, hi]`` (for recorded test data)."""
    keep = lambda evs: [e for e in evs if lo <= e[1] < hi]
    return {"devices": [{"name": d["name"], "ops": keep(d["ops"]),
                         "modules": keep(d["modules"])}
                        for d in tr["devices"]],
            "host": [h for h in tr["host"]
                     if h[0] == WINDOW_SPAN or lo <= h[1] < hi],
            "planes": tr.get("planes", [])}
