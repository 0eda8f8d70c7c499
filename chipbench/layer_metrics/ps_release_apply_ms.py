"""PS server apply, inside a lock-step release: milliseconds from the
release's begin to the mean applied and the merge cleared, a round, over
all servers: kStats ``release_apply_seconds`` over ``sync_rounds``: the
release's arithmetic; ``ps_release_wall_ms`` less this is the replies'
writes.  The job's totals, read from the registry's mirror of the last
kStats read (``ps_server_recv_ms`` says how); nothing where the servers
released no round."""

from chipbench.layer_metrics.ps_server_recv_ms import ms_a


def read(run):
    if not run.get("bsp"):
        return None
    return ms_a("release_apply_seconds", "sync_rounds")
