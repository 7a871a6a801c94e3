"""Milliseconds a fresh request waits from its submission to the slot it is
admitted to, per admission in the window (``ServeStats.queue_time`` /
``admissions``)."""


def read(run):
    if "admissions" not in run.stats1 or not run.delta("admissions"):
        return None
    return 1e3 * run.delta("queue_time") / run.delta("admissions")
