"""Device time of every program other than the decode step and the
prefill (the cache's eager scatters, slices and updates) over the traced
window, in percent."""


def read(run):
    if run.trace is None:
        return None
    times = run.trace.program_time()
    other = sum(s for name, (s, _) in times.items()
                if not name.startswith(("jit_decode_step", "jit_prefill")))
    devices = len(run.trace.programs) or 1
    return 100.0 * other / (run.trace.window_s * devices)
