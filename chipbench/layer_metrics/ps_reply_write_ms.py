"""PS server, a push's life phase by phase: milliseconds one
value-carrying reply's write took, begun to written, over all servers:
kStats ``reply_write_seconds`` over the replies that carry values.  Every
gradient push of the PS cells is fused (one reply of 2 MB a server), so
the replies are ``total_pushes`` but for the one header-only reply to the
seed push.  Written side by side in a lock-step release, each write is
counted, by the thread that wrote it.  The job's totals, read from the
registry's mirror of the last kStats read (``ps_server_recv_ms`` says
how); nothing where the servers count no such thing."""

from chipbench.layer_metrics.ps_server_recv_ms import ms_a


def read(run):
    return (ms_a("reply_write_seconds", "total_pushes")
            if run.get("ps") else None)
