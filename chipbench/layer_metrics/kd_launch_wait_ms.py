"""PS worker round, the keyed job's under bounded delay, seen from the
device: from the start of a worker's ``compute`` annotation to the start
of its own run of ``jit_ps_keyed_grad_step``, averaged over the traced
rounds, in milliseconds, as ``ps_launch_wait_ms`` and
``kf_launch_wait_ms`` read it: the dispatch, the rest of the weights'
copy, and the wait behind the other workers' programs on the one chip,
which grows as the exchange leaves the loop and the four loops meet at
the chip more often.  Nothing where the run carries no such side."""

from chipbench.layer_metrics import ps_launch_wait_ms


def read(run):
    return ps_launch_wait_ms.read(run) if run.get("kd") else None
