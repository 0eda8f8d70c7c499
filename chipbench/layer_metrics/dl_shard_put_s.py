"""Loader, bounded-delay cell: seconds inside the program's ``shard_put``
spans, one a worker: ``shard_put_s``'s reading (the whole shard placed on
the step's device, to ready), under this cell's name."""

from chipbench.layer_metrics.shard_put_s import read  # noqa: F401
