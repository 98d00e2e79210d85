"""Device seconds per sweep of the closed round's departure scan (named
scope ``closed.depart``: the sequential max-plus ``lax.scan`` over the
grid and its transposes), its rounds and its share of the replay
together.  Nothing where the trace has no such scope."""
import scope_reduce


def read(run):
    return scope_reduce.per_sweep(run, "closed.depart")
