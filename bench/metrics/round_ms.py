"""Mean wall time of the window's super-rounds (``SlotStats.round_times``:
admission, the round's dispatch, its done-flag readback and retirement)."""
import numpy as np


def read(run):
    return float(np.mean(run.round_times)) * 1e3 if len(run.round_times) else None
