"""PS server apply, BSP: thread-CPU milliseconds of the push handler a
push (parse, merge and, on the round's last, the release), over the
window and all servers: ``ps_server_push_cpu_ms``'s reading in a run
that counted its servers' rounds; nothing elsewhere."""

from chipbench.layer_metrics import ps_server_push_cpu_ms


def read(run):
    return ps_server_push_cpu_ms.read(run) if run.get("bsp") else None
