"""Loader: seconds inside the program's ``shard_put`` spans, one a
worker: the whole shard placed on the step's device, to ready.  All of
it set-up, read from the registry as ``load_s`` is.  Nothing where the
program records no such span."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    return phase_seconds("shard_put")
