"""Mean live slots per super-round in the window
(``SlotStats.slot_occupancy``)."""
import numpy as np


def read(run):
    return float(np.mean(run.occupancy)) if len(run.occupancy) else None
