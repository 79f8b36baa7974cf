"""Median wait of a query from its scheduled arrival to its ``submit()``:
``pump()`` blocks for the round in flight, so a query that arrives during
a round reaches the runtime only after it.  ``SlotStats.queue_waits``
starts at ``submit()`` and cannot see this wait."""
import numpy as np


def read(run):
    lags = run.window.lags()
    return float(np.median(lags)) if len(lags) else None
