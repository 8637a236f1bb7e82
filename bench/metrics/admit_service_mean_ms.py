"""Serving-engine admission service: mean over requests due in the window
of the time from the engine's admission stamp (``Request.admit_s``) to the
first token: the bucket prefill, the extend chunks and the steps between
them. A request admitted with no first token by the end of the drain counts
up to the drain's end, one never admitted counts 0. With
``admit_wait_mean_ms`` it sums to ``ttft_mean_ms``. None where the program
stamps no admission."""


def read(run):
    recs = run.window_recs()
    if not any(hasattr(r.req, "admit_s") for r in recs):
        return None
    v = []
    for r in recs:
        admit = getattr(r.req, "admit_s", None)
        end = r.stamps[0] if r.stamps else run.drain_end
        v.append(end - admit if admit is not None else 0.0)
    return sum(v) / len(v) * 1e3
