"""PS exchange, seen from the softmax worker's loop: the mean of the
program's ``push`` spans inside the measured ``fit`` calls, in
milliseconds: what the pipeline did not hide of a 4.96 MB exchange (the
loop blocked on the reply to the last round's fused push-pull after this
round's device chain was done).  Nothing where the run carries no such
side or the program records no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "push") if run.get("sm") else None
