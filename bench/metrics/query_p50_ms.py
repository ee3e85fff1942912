"""Median latency of every request answered from the window, timed from its
submission to its response."""
import numpy as np


def read(run):
    lat = [a.latency for a in run.record.answers if a.ok]
    return float(np.percentile(lat, 50) * 1e3) if lat else None
