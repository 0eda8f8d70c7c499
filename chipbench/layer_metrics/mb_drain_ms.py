"""PS exchange, an epoch's end: the mean ``push`` span that is an epoch's
drain (``drain`` among the span's stats), in milliseconds: the whole of
the last round's exchange, with no compute left to run under it.  One
push in ``rounds an epoch`` is such a one: 1 in 3 in the cell, 1 in
89,533 at the source's size.  Nothing where the run carries no such side
or no such span."""

from chipbench.layer_metrics.mb_push_wait_ms import mean_push_ms


def read(run):
    return mean_push_ms(run, "drain")
