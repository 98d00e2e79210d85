"""Share of the traced stretch in which no op ran on a chip, in percent,
the largest over the cell's chips."""


def read(run):
    t = run["trace"]
    return 100.0 * max(1.0 - b / t["window_s"] for b in t["busy_s"])
