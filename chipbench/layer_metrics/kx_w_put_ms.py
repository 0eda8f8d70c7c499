"""PS worker round, the keyed job's: the mean of the program's w_put spans
inside the measured fit calls, in milliseconds: the pulled weights
padded to the shard's key count and handed to the runtime for the step's
device.  Nothing where the run carries no such side or the program
records no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "w_put") if run.get("kx") else None
