"""PS worker round, the keyed job's: the mean of the program's grad_d2h
spans inside the measured fit calls, in milliseconds: the rest of the
gradient's readback, of which the window's own keys' part goes to the
push.  Nothing where the run carries no such side or the program records
no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "grad_d2h") if run.get("kx") else None
