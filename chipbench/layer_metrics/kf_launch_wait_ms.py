"""PS worker round, the keyed job's under FTRL-Proximal servers, seen
from the device: from the start of a worker's ``compute`` annotation to
the start of its own run of ``jit_ps_keyed_grad_step``, averaged over the
traced rounds, in milliseconds, as ``ps_launch_wait_ms`` reads it: the
dispatch, the rest of the weights' copy, and the wait behind the other
workers' programs on the one chip.  Nothing where the run carries no
such side."""

from chipbench.layer_metrics import ps_launch_wait_ms


def read(run):
    return ps_launch_wait_ms.read(run) if run.get("kf") else None
