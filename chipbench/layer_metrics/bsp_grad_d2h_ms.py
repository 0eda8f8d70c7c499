"""PS worker round, BSP: the mean of the program's ``grad_d2h`` spans
inside the measured ``fit`` calls, in milliseconds: the gradient read
back from the device into the buffer the client sends from.  Nothing
where the run is not a BSP job or records no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "grad_d2h") if run.get("bsp") else None
