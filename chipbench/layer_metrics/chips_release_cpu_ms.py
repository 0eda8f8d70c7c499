"""PS server apply, a worker to a chip: thread-CPU milliseconds of one
release (the mean applied, the merge cleared, the W replies written,
under the server's lock on the last voter's thread), over the window and
all servers: ``bsp_release_cpu_ms``'s reading in a run laid out a worker
to a chip, where every reply of the release is on the round's path;
nothing elsewhere."""

from chipbench.layer_metrics import bsp_release_cpu_ms


def read(run):
    return bsp_release_cpu_ms.read(run) if run.get("on_chips") else None
