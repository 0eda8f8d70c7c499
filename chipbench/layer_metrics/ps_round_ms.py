"""PS worker round: a worker's pace inside the measured ``fit`` calls,
the window's wall over the whole-shard iterations each worker ran, in
milliseconds: everything a round costs the loop, the other workers'
turns on the one chip included.  Nothing where the run is not a PS job."""


def read(run):
    ps = run.get("ps")
    if not ps or not ps.get("rounds_per_worker"):
        return None
    return 1e3 * run["window"]["wall_s"] / ps["rounds_per_worker"]
