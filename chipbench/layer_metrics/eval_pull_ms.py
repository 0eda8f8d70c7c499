"""PS exchange, the eval's part: the mean of rank 0's ``eval_pull`` spans
inside the measured ``fit`` call, in milliseconds: the blocking pull of
the whole weight vector that opens an eval (upstream ``src/lr.cc:48``),
answered by servers that already hold the other workers' pushes of the
next round.  Nothing where the program records no such span (one from
before the eval had its phases)."""

from chipbench.layer_metrics.eval_ms import eval_span_ms


def read(run):
    return eval_span_ms(run, "eval_pull")
