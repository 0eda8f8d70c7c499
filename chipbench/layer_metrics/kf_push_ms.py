"""PS exchange, the keyed job's under FTRL-Proximal servers: the mean of
the program's push spans inside the measured fit calls, in milliseconds:
a keyed push of a window's unique keys and their gradient, sent, stepped
key by key on the two servers and acknowledged, the loop blocked on it.
Nothing where the run carries no such side or the program records no
such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "push") if run.get("kf") else None
