"""PS server apply, BSP: thread-CPU milliseconds of one release (the mean
applied over the server's range, the merge cleared, the W replies
gathered and written, all under the server's lock on the last voter's
thread), over the window and all servers: the rise of kStats'
``cpu_release_seconds`` over the rise of ``sync_rounds``.  Nothing where
the servers count no rounds."""


def read(run):
    bsp = run.get("bsp")
    if not bsp or not bsp.get("server_rounds"):
        return None
    return 1e3 * bsp["release_cpu_s"] / bsp["server_rounds"]
