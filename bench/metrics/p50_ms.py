"""Median latency, due time to answer on the host, of every request due
in the window (one with no answer counts as late as the drain's end)."""
import numpy as np


def value(run):
    return float(np.percentile(run.latencies_s(), 50)) * 1e3
