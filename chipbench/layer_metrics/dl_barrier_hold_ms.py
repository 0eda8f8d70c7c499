"""PS server barrier, under bounded delay: milliseconds a push stood at
the barrier, from its arrival at a server to its own reply written, over
the window and all servers: ``bsp_barrier_hold_ms``'s reading (the rise
of kStats' ``sync_hold_seconds`` over the rise of ``total_pushes``) in a
run that counted its delayed rounds.  The workers no longer wait for it:
their next gradient runs meanwhile.  Nothing elsewhere."""

from chipbench.layer_metrics import bsp_barrier_hold_ms


def read(run):
    return bsp_barrier_hold_ms.read(run) if run.get("dl") else None
