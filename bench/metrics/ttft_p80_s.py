"""80th percentile, over the requests due in the window, of the seconds from
when each was due to its first token. A request with no first token by the
window's close counts with its wait so far."""
import numpy as np


def read(run):
    waits = run.first_token_waits()
    return float(np.percentile(waits, 80)) if waits else None
