"""PS worker round, the keyed job's: a worker's pace inside the measured
``fit`` calls, the window's wall over the keyed rounds each worker ran,
in milliseconds: a keyed pull, the window's gradient on the chip, a keyed
push, and the other workers' turns on the one chip.  Nothing where the
run carries no such side."""


def read(run):
    kx = run.get("kx")
    if not kx or not kx.get("rounds_per_worker"):
        return None
    return 1e3 * run["window"]["wall_s"] / kx["rounds_per_worker"]
