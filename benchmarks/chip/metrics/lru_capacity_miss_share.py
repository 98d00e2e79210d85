"""Share of the simulated operations that miss their leader's page cache
although their key was served earlier in the same queue (evicted since),
in percent: the program's counter ``capacity_misses`` (read from the
converged round) over the folded ops, summed over the window's sweeps.
Nothing where a sweep reports no such counter."""


def read(run):
    misses = [s.get("capacity_misses") for s in run["sweeps"]]
    ops = sum(s["ops"] for s in run["sweeps"])
    if not misses or None in misses or not ops:
        return None
    return 100.0 * sum(misses) / ops
