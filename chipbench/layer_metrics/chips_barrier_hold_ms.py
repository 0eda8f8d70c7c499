"""PS server barrier, a worker to a chip: milliseconds a push stood at
the barrier, from its arrival at a server to its own reply written, over
the window and all servers: ``bsp_barrier_hold_ms``'s reading in a run
laid out a worker to a chip; nothing elsewhere."""

from chipbench.layer_metrics import bsp_barrier_hold_ms


def read(run):
    return bsp_barrier_hold_ms.read(run) if run.get("on_chips") else None
