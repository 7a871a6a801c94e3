"""Median of the gaps between consecutive output tokens of one request, both
inside the window, over every gap of the window. A gap still open when the
window closes counts with its length so far."""
import numpy as np


def read(run):
    gaps = run.token_gaps()
    return 1e3 * float(np.percentile(gaps, 50)) if gaps else None
