"""PS server apply, under FTRL-Proximal: nanoseconds a coordinate's
step, the window's rise of kStats ``merge_seconds`` over its rise of
``ftrl_steps``, over all servers: two square roots, two divides, a
branch and three scattered tables a key, scalar, under the lock: the
number a packed ``FtrlStep`` has to beat.  Nothing where the run carries
no such side or the servers count no step."""


def read(run):
    kf = run.get("kf")
    if not kf or not kf.get("ftrl_steps") or not kf.get("server_merge_s"):
        return None
    return 1e9 * kf["server_merge_s"] / kf["ftrl_steps"]
