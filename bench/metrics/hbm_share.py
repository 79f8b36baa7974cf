"""The least bytes the window's completed queries needed (bench/harness/
roofline.py, counted from the reference's frontiers) over the device's busy
time in the traced window, as a share of the chip's peak HBM bandwidth."""


def read(run):
    if run.trace is None or not run.least_bytes:
        return None
    return 100.0 * run.least_bytes / run.trace["busy_s"] / run.peaks["hbm_bw"]
