"""PS worker round, BSP under bounded delay, seen from the device: from
the start of a worker's ``compute`` annotation to the start of its own
run of the gradient program, averaged over the traced rounds, in
milliseconds: the wait behind the other workers' programs on the one
chip.  ``ps_launch_wait_ms``'s reading in a run that counted its delayed
rounds; nothing elsewhere."""

from chipbench.layer_metrics import ps_launch_wait_ms


def read(run):
    return ps_launch_wait_ms.read(run) if run.get("dl") else None
