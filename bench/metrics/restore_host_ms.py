"""Host milliseconds per resume in ``restore_slot``: the concatenate of the
reloaded blocks, their ``device_put`` and the scatter's dispatch
(``ServeStats.restore_time`` / ``restores``, the ``serve.kv.restore_slot``
span)."""


def read(run):
    if "restores" not in run.stats1 or not run.delta("restores"):
        return None
    return 1e3 * run.delta("restore_time") / run.delta("restores")
