"""Serving-engine admission: mean over requests due in the window of the
time from due to the start of the engine step that took the request out of
its waiting queue (host clock). A request still queued when the drain ended
counts its whole wait."""


def read(run):
    v = [((r.left_queue if r.left_queue is not None else run.drain_end)
          - r.due) for r in run.window_recs() if not (r.failed and
                                                      r.req is None)]
    return sum(v) / len(v) * 1e3 if v else None
