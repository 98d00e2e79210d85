"""Device seconds per sweep of the closed round's page penalties (named
scope ``closed.lru``: the segment take, ``segment_min`` and the service
times), its rounds and its share of the replay together.  Nothing where
the trace has no such scope."""
import scope_reduce


def read(run):
    return scope_reduce.per_sweep(run, "closed.lru")
