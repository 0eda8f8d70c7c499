"""PS server apply, under FTRL-Proximal, the keyed job's under bounded
delay: a server's milliseconds a keyed push with its lock held, the
window's rise of kStats ``merge_seconds`` over its rise of
``total_pushes``, over all servers, as ``kf_server_apply_ms`` reads it:
the delay changes when a push arrives, not what applying it costs.
Nothing where the run carries no such side or the servers count no such
thing."""


def read(run):
    kd = run.get("kd")
    if not kd or not kd.get("server_pushes") or not kd.get("server_merge_s"):
        return None
    return 1e3 * kd["server_merge_s"] / kd["server_pushes"]
