"""Mean of every gap between consecutive tokens of a request whose later
token was emitted in the window: steps that decode and steps that also carry
an admission chunk, each gap counted once."""


def read(run):
    gaps = [t - r.stamps[j - 1] for r, j, t in run.token_times() if j > 0]
    return sum(gaps) / len(gaps) * 1e3 if gaps else None
