"""PS exchange: the mean of the program's ``wire`` spans inside the
measured ``fit`` calls, in milliseconds: one fused push-pull on the comm
thread, send to reply: the client's slicing, loopback both ways, and the
servers' apply and copy-out between.  Nothing where the program records
no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "wire")
