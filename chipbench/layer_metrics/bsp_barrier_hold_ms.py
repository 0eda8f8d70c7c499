"""PS server barrier: milliseconds a push stood at the barrier, from its
arrival at a server to its own reply written, over the window and all
servers: the rise of kStats' ``sync_hold_seconds`` over the rise of
``total_pushes``.  Nothing where the servers count no rounds."""


def read(run):
    bsp, ps = run.get("bsp"), run.get("ps")
    if not bsp or not bsp.get("server_rounds") or not ps.get("server_pushes"):
        return None
    return 1e3 * bsp["hold_s"] / ps["server_pushes"]
