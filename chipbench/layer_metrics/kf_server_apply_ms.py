"""PS server apply, under FTRL-Proximal: a server's milliseconds a keyed
push with its lock held, the window's rise of kStats ``merge_seconds``
over its rise of ``total_pushes``, over all servers: the FTRL step of
every pushed key whose entry is not zero, where the keys lie
(``ApplySpan`` over rows that are no run), and the reply's header.  The
driver read both kStats before and after the window.  Nothing where the
run carries no such side or the servers count no such thing."""


def read(run):
    kf = run.get("kf")
    if not kf or not kf.get("server_pushes") or not kf.get("server_merge_s"):
        return None
    return 1e3 * kf["server_merge_s"] / kf["server_pushes"]
