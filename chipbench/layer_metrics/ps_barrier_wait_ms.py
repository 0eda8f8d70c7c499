"""PS server barrier: milliseconds a push, merged, waited for the later
arrivals of its round, over all servers: kStats ``sync_wait_seconds``
(a push's merge done to its round's release begun; 0 for the last voter)
over ``total_pushes``.  The barrier's hold (``bsp_barrier_hold_ms``,
``chips_barrier_hold_ms``) less the merge less this is a reply's place in
the release.  The job's totals, read from the registry's mirror of the
last kStats read (``ps_server_recv_ms`` says how); nothing where the
servers released no round."""

from chipbench.layer_metrics.ps_server_recv_ms import ms_a, stat_sum


def read(run):
    if not run.get("bsp") or not stat_sum("sync_rounds"):
        return None
    return ms_a("sync_wait_seconds", "total_pushes")
