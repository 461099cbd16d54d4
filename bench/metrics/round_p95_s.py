"""95th percentile of the window's round times (host clock, each round
ending in block_until_ready), over all rounds of the window."""

import numpy as np


def read(record):
    if not record.window.seconds:
        return None
    return float(np.percentile(np.asarray(record.window.seconds), 95))
