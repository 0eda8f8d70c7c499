"""PS server apply, under FTRL-Proximal: milliseconds a push handler
stood waiting for the server's one lock before its apply could begin,
over the window and all servers: the rise of kStats
``lock_wait_seconds`` over the rise of ``total_pushes``.  Near zero
where pushes arrive apart; where an apply is long beside a round, the
four workers' pushes queue on the two locks and this is what they pay
first.  Nothing where the run carries no such side or the servers
counted no push."""


def read(run):
    kf = run.get("kf")
    if not kf or not kf.get("server_pushes") or "lock_wait_s" not in kf:
        return None
    return 1e3 * kf["lock_wait_s"] / kf["server_pushes"]
