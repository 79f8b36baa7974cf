"""Median latency, from scheduled arrival to the pump() that returned the
answer, over every query that arrived in the window (drained ones too)."""
import numpy as np


def read(run):
    lat = run.window.latencies()
    return float(np.percentile(lat, 50)) if len(lat) else None
