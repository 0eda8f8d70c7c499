"""PS worker round, BSP: the mean of the program's ``w_put`` spans inside
the measured ``fit`` calls, in milliseconds: the round's weights placed
on the step's device, to ready (each of the four workers places the same
weights).  Nothing where the run is not a BSP job or records no such
span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "w_put") if run.get("bsp") else None
