"""Share of the window in which the engine had no resident row to step
(``ServeStats.stall_time``), per replica, in percent."""


def read(run):
    replicas = len({eng for _, eng, _, _ in run.decode}) or 1
    return 100.0 * run.delta("stall_time") / (run.seconds * replicas)
