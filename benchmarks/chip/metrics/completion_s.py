"""Device seconds per sweep of the closed round's completion stage (named
scope ``closed.completion``: the completion chain), its rounds and its
share of the replay together.  Nothing where the trace has no such
scope."""
import scope_reduce


def read(run):
    return scope_reduce.per_sweep(run, "closed.completion")
