"""Model step, decode side: mean device time of one call of the decode
program in the traced window."""
from bench import trace as T


def read(run):
    if not run.trace or not run.trace["devices"]:
        return None
    lo, hi = T.window(run.trace)
    s, n = T.module_time(run.trace["devices"][0], lo, hi, "decode")
    return s * 1e3 / n if n else None
