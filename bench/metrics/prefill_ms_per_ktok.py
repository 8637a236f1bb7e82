"""Model step, admission side: device time of the prefill and extend
programs in the traced window per 1000 prompt tokens admitted in it. The
engine jits ``prefill_fn`` through a ``functools.partial``, which XLA names
``jit__unknown``; no other program of the async paged path is unnamed."""
from bench import trace as T


def read(run):
    if not run.trace or not run.trace["devices"]:
        return None
    lo, hi = T.window(run.trace)
    dev = run.trace["devices"][0]
    s = sum(T.module_time(dev, lo, hi, p)[0]
            for p in ("prefill", "extend", "_unknown"))
    a, b = run.win
    toks = sum(len(r.arrival.prompt) for r in run.recs
               if r.left_queue is not None and a <= r.left_queue < b)
    if s <= 0 or toks == 0:
        return None
    return s * 1e3 / (toks / 1e3)
