"""PS server apply, a worker to a chip: milliseconds a push handler
stood waiting for the server's one lock before its merge could begin,
over the window and all servers: the rise of kStats'
``lock_wait_seconds`` over the rise of ``total_pushes``.  Near zero
where pushes arrive apart; with four at once it is the first thing a
merge pays.  Nothing where the run is not laid out a worker to a chip or
the servers counted no push."""


def read(run):
    chips, ps = run.get("on_chips"), run.get("ps")
    if not chips or not ps or not ps.get("server_pushes"):
        return None
    return 1e3 * chips["lock_wait_s"] / ps["server_pushes"]
