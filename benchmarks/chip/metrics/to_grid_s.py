"""Device seconds per sweep of the closed round's fill of the scan grid
(named scope ``closed.to_grid``: the two static gathers through ``src``
that move arrivals and service times into the (rows, longest queue)
grid), its rounds and its share of the replay together.  Nothing where
the trace has no such scope."""
import scope_reduce


def read(run):
    return scope_reduce.per_sweep(run, "closed.to_grid")
