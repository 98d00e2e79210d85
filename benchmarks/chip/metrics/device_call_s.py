"""Seconds of the device call as the host sees it (transfer, the jitted
program, ``device_get``), mean over the window's sweeps."""
from statistics import fmean


def read(run):
    return fmean(s["device_s"] for s in run["sweeps"])
