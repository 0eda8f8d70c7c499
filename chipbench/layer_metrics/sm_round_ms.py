"""PS worker round, the softmax worker's: the window's wall over the
whole-shard iterations each worker ran, in milliseconds: everything a
round costs the loop, the other three workers' programs queued on the
one chip included.  Nothing where the run carries no such side."""


def read(run):
    side = run.get("sm")
    if not side or not side.get("rounds_per_worker"):
        return None
    return 1e3 * run["window"]["wall_s"] / side["rounds_per_worker"]
