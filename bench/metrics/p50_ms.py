"""Median latency of every request of the window, from when it was due
to when its answer was ready."""
import numpy as np


def read(run):
    lat = run["latency_ms"]
    return None if lat is None or lat.size == 0 else np.percentile(lat, 50)
