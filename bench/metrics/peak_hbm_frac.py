"""The chip's ``peak_bytes_in_use`` after the window over its HBM in the
peaks table."""


def read(run):
    if not run.memory_peak_bytes or not run.peak:
        return None
    return run.memory_peak_bytes / run.peak["hbm_bytes"]
