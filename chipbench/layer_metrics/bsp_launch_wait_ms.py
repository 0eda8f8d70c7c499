"""PS worker round, BSP, seen from the device: from the start of a
worker's ``compute`` annotation to the start of its own run of the
gradient program, averaged over the traced rounds, in milliseconds.
After a release the four workers start together, so this is mostly the
wait behind the others' programs on the one chip.
``ps_launch_wait_ms``'s reading in a run that counted its servers'
rounds; nothing elsewhere."""

from chipbench.layer_metrics import ps_launch_wait_ms


def read(run):
    return ps_launch_wait_ms.read(run) if run.get("bsp") else None
