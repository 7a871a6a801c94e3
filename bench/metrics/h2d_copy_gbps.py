"""Host-to-device copy rate of the h2d stream: bytes of the reloaded
blocks it staged on the device over the seconds of its ``serve.h2d.copy``
spans (``ServeStats.h2d_copy_bytes`` / ``h2d_copy_time``), in GB/s."""


def read(run):
    if "h2d_copy_time" not in run.stats1 or not run.delta("h2d_copy_time"):
        return None
    return run.delta("h2d_copy_bytes") / run.delta("h2d_copy_time") / 1e9
