"""Device seconds per sweep of the closed round's exact LRU stack
distance (named scope ``closed.lru_stack``: the segment take, the two
sorts that find each op's previous same-key op, and the pairwise count
of the distinct keys between them), its rounds and its share of the
replay together.  Nothing where the trace has no such scope, as in a
grid whose leaders cannot evict."""
import scope_reduce


def read(run):
    return scope_reduce.per_sweep(run, "closed.lru_stack")
