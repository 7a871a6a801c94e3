"""Programs compiled, or fetched from the persistent compilation cache,
inside the window (JAX's backend-compile events)."""


def read(run):
    return float(sum(run.in_window(t) for t in run.compiles))
