"""PS worker round, the keyed job's under FTRL-Proximal servers: a
worker's pace inside the measured ``fit`` calls, the window's wall over
the keyed rounds each worker ran, in milliseconds: a keyed pull, the
window's gradient on the chip, a keyed push that the servers step key by
key before they acknowledge it, and the other workers' turns on the one
chip and the two locks.  Nothing where the run carries no such side."""


def read(run):
    kf = run.get("kf")
    if not kf or not kf.get("rounds_per_worker"):
        return None
    return 1e3 * run["window"]["wall_s"] / kf["rounds_per_worker"]
