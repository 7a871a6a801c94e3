"""Share of the window the engine run loop spent waiting for the engine
lock (``ServeStats.lock_wait_time``, the ``serve.loop.lock_wait`` span), per
replica, in percent."""


def read(run):
    if "lock_wait_time" not in run.stats1:
        return None
    replicas = len({eng for _, eng, _, _ in run.decode}) or 1
    return 100.0 * run.delta("lock_wait_time") / (run.seconds * replicas)
