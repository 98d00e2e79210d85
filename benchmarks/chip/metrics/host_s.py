"""Host seconds of one sweep: ``run_sweep``'s wall time less its device
call (plan build, padding and the fold), mean over the window."""
from statistics import fmean


def read(run):
    return fmean(s["walltime_s"] - s["device_s"] for s in run["sweeps"])
