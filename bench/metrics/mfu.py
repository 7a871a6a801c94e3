"""Model FLOPs of every prompt and output token processed in the window,
over the window times the chips' bf16 peak, in percent."""


def read(run):
    dec, pre = run.model_flops()
    chips = len({eng for _, eng, _, _ in run.decode}) or 1
    if not dec + pre:
        return None
    return 100.0 * (dec + pre) / (run.seconds * chips
                                  * run.peaks["bf16_flops_per_s"])
