"""Share of the closed fixed point's op updates that changed a
completion, in percent: the program's counters ``changed`` over
``op_rounds`` (rounds x real ops), summed over the window's sweeps.
Nothing where a sweep reports no such counters."""


def read(run):
    changed = [s.get("changed") for s in run["sweeps"]]
    op_rounds = [s.get("op_rounds") for s in run["sweeps"]]
    if not changed or None in changed + op_rounds or not sum(op_rounds):
        return None
    return 100.0 * sum(changed) / sum(op_rounds)
