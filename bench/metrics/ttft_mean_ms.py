"""Mean, over every request due in the window, of the time from its due
time to its first token. A request with no first token by the end of the
drain counts the whole wait it was given."""


def read(run):
    v = [run.ttft_s(r) for r in run.window_recs()]
    return sum(v) / len(v) * 1e3 if v else None
