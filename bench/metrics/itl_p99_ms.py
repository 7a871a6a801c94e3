"""99th percentile of the gaps between consecutive output tokens of one
request, both inside the window. A gap still open when the window closes
counts with its length so far."""
import numpy as np


def read(run):
    gaps = []
    for rid, times in run.tokens.items():
        inside = [t for t in times if run.in_window(t)]
        gaps += list(np.diff(inside))
        if inside and run.done.get(rid, run.t1) >= run.t1:
            gaps.append(run.t1 - inside[-1])
    if not gaps:
        return None
    return 1e3 * float(np.percentile(gaps, 99))
