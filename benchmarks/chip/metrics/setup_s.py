"""Process start to window start: imports, device start, data from the
seed, compile-cache reads and compiles, warm-up (host clock)."""


def read(run):
    return run.setup_s
