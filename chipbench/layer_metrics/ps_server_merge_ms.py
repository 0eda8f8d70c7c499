"""PS server, a push's life phase by phase: milliseconds from the
server's lock held to the push's own arithmetic done, a push, over all
servers: kStats ``merge_seconds`` over ``total_pushes``: in lock step the
merge of the gradient into the round's sum; in an asynchronous job the
apply over the range and the copy of the reply's values.  The release is
not in it.  The job's totals, read from the registry's mirror of the
last kStats read (``ps_server_recv_ms`` says how); nothing where the
servers count no such thing."""

from chipbench.layer_metrics.ps_server_recv_ms import ms_a


def read(run):
    return ms_a("merge_seconds", "total_pushes") if run.get("ps") else None
