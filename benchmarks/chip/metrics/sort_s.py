"""Device seconds per sweep of the closed round's queue order (named scope
``closed.order``: the composite stable ``lax.sort`` by row and arrival),
its rounds and its share of the replay together.  Nothing where the
trace has no such scope; a sort under another scope does not count."""
import scope_reduce


def read(run):
    return scope_reduce.per_sweep(run, "closed.order")
