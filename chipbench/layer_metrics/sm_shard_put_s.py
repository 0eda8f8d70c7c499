"""Loader, the softmax job's: seconds inside the program's ``shard_put``
spans, one a worker: a 0.985 GB shard placed on the step's device, to
ready (a plain ``device_put``: ``feed.place`` takes no row of 62,061
words as held, so the runtime's host threads relay it).  All of it
set-up, read from the registry as ``shard_put_s`` is.  Nothing where the
run carries no such side or the program records no such span."""

from chipbench.layer_metrics.load_s import phase_seconds


def read(run):
    return phase_seconds("shard_put") if run.get("sm") else None
