"""Device seconds per sweep of the closed round's arrival stage (named
scope ``closed.arrival``: the predecessor's completion taken and the
arrival chain), its rounds and its share of the replay together.
Nothing where the trace has no such scope."""
import scope_reduce


def read(run):
    return scope_reduce.per_sweep(run, "closed.arrival")
