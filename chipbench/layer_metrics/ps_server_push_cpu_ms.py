"""PS server apply: thread-CPU milliseconds a server spent in its push
handler for each push it applied, over the window and all servers: the
rise of kStats' ``cpu_push_seconds`` over the rise of ``total_pushes``
(a worker's dense push is one push on every server).  Nothing where the
servers report no such counter."""


def read(run):
    ps = run.get("ps")
    if not ps or not ps.get("server_pushes") or not ps.get("server_push_cpu_s"):
        return None
    return 1e3 * ps["server_push_cpu_s"] / ps["server_pushes"]
