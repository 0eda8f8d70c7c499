"""Loader, a worker to a chip: seconds inside the program's
``shard_put`` spans, one a worker, each the whole shard placed on the
worker's own chip, to ready, one after another: ``shard_put_s``'s
reading in a run laid out a worker to a chip; nothing elsewhere."""

from chipbench.layer_metrics import shard_put_s


def read(run):
    return shard_put_s.read(run) if run.get("on_chips") else None
