"""PS server barrier: milliseconds between a round's first push arriving
at a server and its last, over the window and all servers: the rise of
kStats' ``sync_spread_seconds`` over the rise of ``sync_rounds``: how far
apart the workers reach the barrier (on one chip, the four programs run
one after another).  Nothing where the servers count no rounds."""


def read(run):
    bsp = run.get("bsp")
    if not bsp or not bsp.get("server_rounds"):
        return None
    return 1e3 * bsp["spread_s"] / bsp["server_rounds"]
