"""Loader, the eval's split: seconds inside the program's ``test_put``
span: rank 0's whole test split placed on the eval's device, to ready,
once, by the first eval (set-up's recorded phase).  Read from the
registry as ``shard_put_s`` is.  Nothing where the program records no
such span (one that streams the split at every eval)."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    return phase_seconds("test_put") if run.get("eval") else None
