"""Milliseconds from a request's preemption to its running again, per
resume (``ServeStats.swap_stall_time`` / ``resumes``)."""


def read(run):
    if "resumes" not in run.stats1 or not run.delta("resumes"):
        return None
    return 1e3 * run.delta("swap_stall_time") / run.delta("resumes")
