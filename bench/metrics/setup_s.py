"""Process start to window open: loading, weights, warm-up (compilation or
loading from the persistent cache) and the traffic lead-in."""


def read(run):
    return run.setup_s
