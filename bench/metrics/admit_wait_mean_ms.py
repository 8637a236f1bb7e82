"""Serving-engine admission: mean over requests due in the window of the
time from due to the engine's own stamp of when the request left its
queue for a slot (``Request.admit_s``, host clock). A request not admitted
by the end of the drain counts its whole wait. With
``admit_service_mean_ms`` it sums to ``ttft_mean_ms``. None where the
program stamps no admission."""


def read(run):
    recs = run.window_recs()
    if not any(hasattr(r.req, "admit_s") for r in recs):
        return None
    v = []
    for r in recs:
        admit = getattr(r.req, "admit_s", None)
        v.append((admit if admit is not None else run.drain_end) - r.due)
    return sum(v) / len(v) * 1e3
