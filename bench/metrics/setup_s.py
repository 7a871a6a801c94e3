"""Process start to window start: loading, weights, compiling or loading
compiled programs, and the warm-up traffic."""


def read(run):
    return run.setup_s
