"""Share of the departure scan's grid slots that hold a real op, in
percent: the folded ops over the program's counter ``grid_slots``
(blocks x rows x longest queue), summed over the window's sweeps.
Nothing where a sweep reports no such counter."""


def read(run):
    slots = [s.get("grid_slots") for s in run["sweeps"]]
    if not slots or None in slots or not sum(slots):
        return None
    return 100.0 * sum(s["ops"] for s in run["sweeps"]) / sum(slots)
