"""PS exchange, a worker to a chip: the mean of the program's ``push``
spans inside the measured ``fit`` calls, in milliseconds: the fused
push-pull on the loop's own thread, from the send to the weights after
the round: four sends at once, a merge behind up to three others under
the server's lock, and a release whose last reply is on the round's
path.  Nothing where the run is not laid out a worker to a chip."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "push") if run.get("on_chips") else None
