"""PS exchange, the keyed job's under FTRL-Proximal servers: the mean of
the program's pull spans inside the measured fit calls, in milliseconds:
a keyed pull of a window's unique keys, sent, answered and read, the
loop blocked on it; a pull stands behind whatever push holds the
server's lock.  Nothing where the run carries no such side or the
program records no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "pull") if run.get("kf") else None
