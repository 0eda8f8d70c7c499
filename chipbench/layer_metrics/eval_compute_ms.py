"""PS worker eval: the mean of rank 0's ``eval_compute`` spans inside the
measured ``fit`` call, in milliseconds: dispatch of the eval program to
accuracy and logloss ready on the device: the one forward pass over the
resident test rows and, on one chip, the wait behind whatever the other
workers still have queued there.  Nothing where the program records no
such span."""

from chipbench.layer_metrics.eval_ms import eval_span_ms


def read(run):
    return eval_span_ms(run, "eval_compute")
