"""The prefills' least time on this chip for the work they need (every real
prompt token through every layer, causal attention over the real lengths,
the last position's logits), over the device time of ``jit_prefill``, in
percent."""


def read(run):
    return run.roofline("prefill", "jit_prefill")
