"""The decode steps' least time on this chip for the work they need
(weights, each row's live KV, the new tokens, the logits), over the device
time of ``jit_decode_step``, in percent."""


def read(run):
    return run.roofline("decode", "jit_decode_step")
