"""PS exchange, BSP: the mean of the program's ``push`` spans inside the
measured ``fit`` calls, in milliseconds: the fused push-pull on the
loop's own thread, from the send to the weights after the round: wire,
the wait for the slowest worker's push, and the release.  Nothing where
the run is not a BSP job or records no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "push") if run.get("bsp") else None
