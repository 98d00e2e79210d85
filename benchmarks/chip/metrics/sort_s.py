"""Device seconds per sweep of the round's composite ``lax.sort``.
Nothing where the trace lacks the structure this assumes: one sort in
every round, and one in the replay of the converged round."""


def read(run):
    t = run["trace"]
    s = t["groups"].get("sort")
    if not s or not t["sweeps"] or run["rounds"] is None:
        return None
    if t["runs"]["sort"] != run["rounds"] + 1:
        return None
    return s / t["sweeps"]
