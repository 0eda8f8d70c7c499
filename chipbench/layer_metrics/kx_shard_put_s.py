"""Loader, the keyed job's: seconds inside the program's ``shard_put``
spans, one a worker: the localised shard's places, values, labels and
real-row flags placed on the step's device, to ready, lane-dense and in
the form they stay in.  All of it set-up, read from the registry as
``shard_put_s`` is.  Nothing where the run carries no such side or the
program records no such span."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    return phase_seconds("shard_put") if run.get("kx") else None
