"""Loader, PS plane: seconds inside the workers' ``load_data`` spans, one
a worker: the shard parsed and densified, the step's device picked, a
whole-shard batch placed there (``shard_put_s`` is that last part).  All
of it set-up, read from the registry as ``load_s`` is.  Nothing where
the program records no such span."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    return phase_seconds("load_data")
