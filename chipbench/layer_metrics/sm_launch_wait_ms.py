"""PS worker round, the softmax worker's, seen from the device: from the
start of a worker's ``compute`` annotation to the start of its own run
of ``jit_ps_grad_step``, averaged over the traced rounds, in
milliseconds, as ``ps_launch_wait_ms`` reads it.  With programs this
long it is most of ``step_ms``: the wait behind the other three workers'
programs on the one chip, the part a single dispatch for four steps
would remove.  Nothing where the run carries no such side."""

from chipbench.layer_metrics import ps_launch_wait_ms


def read(run):
    return ps_launch_wait_ms.read(run) if run.get("sm") else None
