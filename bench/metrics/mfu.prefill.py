"""Model FLOPs of the prompts prefilled in the window, over the engine's
wall seconds in its prefill calls (the ``serve.prefill`` span, launch to
logits on the host: ``ServeStats.prefill_time``) times the chip's bf16
peak, in percent: the whole prefill step's share of the peak."""


def read(run):
    if "prefill_time" not in run.stats1 or not run.delta("prefill_time"):
        return None
    _, flops = run.model_flops()
    if not flops:
        return None
    return 100.0 * flops / (run.delta("prefill_time")
                            * run.peaks["bf16_flops_per_s"])
