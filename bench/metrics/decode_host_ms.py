"""Host milliseconds per decode call beyond the decode program's device
time: the engine's wall time per call (launch, logits copy, sampling) minus
the device time per ``jit_decode_step`` execution in the trace."""


def read(run):
    if run.trace is None or not run.delta("decode_steps"):
        return None
    dev_s, n = run.program("jit_decode_step")
    if not n:
        return None
    host_ms = 1e3 * run.delta("decode_time") / run.delta("decode_steps")
    return host_ms - 1e3 * dev_s / n
