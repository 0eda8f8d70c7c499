"""PS server barrier: milliseconds a lock-step release lasted, from the
last voter's merge done to the last reply written, a round, over all
servers: kStats ``release_wall_seconds`` over ``sync_rounds``: the mean
applied, the merge cleared, W replies written; the server's lock is held
that long.  The job's totals, read from the registry's mirror of the last
kStats read (``ps_server_recv_ms`` says how); nothing where the servers
released no round."""

from chipbench.layer_metrics.ps_server_recv_ms import ms_a


def read(run):
    if not run.get("bsp"):
        return None
    return ms_a("release_wall_seconds", "sync_rounds")
