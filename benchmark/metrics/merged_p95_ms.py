"""95th percentile of op latency, due -> settled round, over every op
due in the window (open-loop cells)."""
import numpy as np


def read(rec):
    if rec["loop"] != "open":
        return None
    return float(np.percentile(rec["latency_ms"], 95))
