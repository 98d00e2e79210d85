"""Device seconds per sweep of the departure scan: the sequential
``lax.scan`` inside the fixed point's round loop.  Nothing where the
trace lacks the structure this assumes: a scan step that runs once per
slot of the longest queue in every round."""


def read(run):
    t = run["trace"]
    s = t["groups"].get("scan")
    if not s or not t["sweeps"] or run["rounds"] is None:
        return None
    if t["runs"]["scan"] != run["rounds"] * run["queue_len"]:
        return None
    return s / t["sweeps"]
