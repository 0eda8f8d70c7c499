"""PS worker round, a worker to a chip: the mean of the program's
``grad_d2h`` spans inside the measured ``fit`` calls, in milliseconds:
the gradient read back from the worker's own chip, four device-to-host
links at once.  Nothing where the run is not laid out a worker to a
chip."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "grad_d2h") if run.get("on_chips") else None
