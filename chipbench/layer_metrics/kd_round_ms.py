"""PS worker round, the keyed job's under bounded delay (tau = 1): a
worker's pace inside the measured ``fit`` calls, the window's wall over
the keyed rounds each worker ran, in milliseconds: the loop's own chain
(the weights' hand-over, the window's gradient on the chip, its
readback, the loop's Python) where the comm thread's push and pull fit
under it, the comm thread's where they do not, and the other workers'
turns on the one chip and the two locks.  Nothing where the run carries
no such side."""


def read(run):
    kd = run.get("kd")
    if not kd or not kd.get("rounds_per_worker"):
        return None
    return 1e3 * run["window"]["wall_s"] / kd["rounds_per_worker"]
