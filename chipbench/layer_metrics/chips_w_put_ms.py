"""PS worker round, a worker to a chip: the mean of the program's
``w_put`` spans inside the measured ``fit`` calls, in milliseconds: the
round's weights placed on the worker's own chip, to ready, four
host-to-device links at once.  Nothing where the run is not laid out a
worker to a chip."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "w_put") if run.get("on_chips") else None
