"""PS worker round: the mean of the program's ``w_put`` spans inside the
measured ``fit`` calls, in milliseconds: the weights a round computes on
placed on the step's device, to ready.  Nothing where the program
records no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "w_put")
