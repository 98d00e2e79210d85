"""Host seconds of ``run_sweep``'s build (the program's span
``run_sweep.build``: schedules, routes, padding and bit patterns), mean
over the window's sweeps.  Nothing where a sweep reports no such span."""
from statistics import fmean


def read(run):
    name = "run_sweep.build"
    v = [(s.get("spans") or {}).get(name) for s in run["sweeps"]]
    return fmean(v) if v and None not in v else None
