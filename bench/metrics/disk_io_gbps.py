"""File I/O rate of the disk tier: bytes spilled and loaded over the
seconds of the ``serve.disk.spill``, ``serve.disk.load`` and
``serve.disk.prefetch`` spans (``ServeStats.disk_io_time``), in GB/s."""


def read(run):
    if "disk_io_time" not in run.stats1 or not run.delta("disk_io_time"):
        return None
    moved = run.delta("disk_spill_bytes") + run.delta("disk_load_bytes")
    return moved / run.delta("disk_io_time") / 1e9
