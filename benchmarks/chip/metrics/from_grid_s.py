"""Device seconds per sweep of the closed round's gather out of the scan
grid (named scope ``closed.from_grid``: the departures gathered from the
grid and scattered back to op order, and the replay's service starts),
its rounds and its share of the replay together.  Nothing where the
trace has no such scope."""
import scope_reduce


def read(run):
    return scope_reduce.per_sweep(run, "closed.from_grid")
