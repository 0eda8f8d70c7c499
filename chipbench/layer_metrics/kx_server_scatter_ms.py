"""PS server apply, the scattered side: a server's milliseconds a keyed
push, the window's rise of kStats ``merge_seconds`` over its rise of
``total_pushes``, over all servers: in the asynchronous job the apply of
a push's single rows where they lie (``ApplySpan`` over rows that are no
run) and the reply's copy.  The driver read both kStats before and after
the window; nothing where the run carries no such side or the servers
count no such thing."""


def read(run):
    kx = run.get("kx")
    if not kx or not kx.get("server_pushes") or not kx.get("server_merge_s"):
        return None
    return 1e3 * kx["server_merge_s"] / kx["server_pushes"]
