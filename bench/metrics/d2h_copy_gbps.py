"""Device-to-host copy rate of the d2h stream: bytes of the blocks it
read over the seconds of its ``serve.d2h.copy`` spans
(``ServeStats.d2h_copy_bytes`` / ``d2h_copy_time``), in GB/s."""


def read(run):
    if "d2h_copy_time" not in run.stats1 or not run.delta("d2h_copy_time"):
        return None
    return run.delta("d2h_copy_bytes") / run.delta("d2h_copy_time") / 1e9
