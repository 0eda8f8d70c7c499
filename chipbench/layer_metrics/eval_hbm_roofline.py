"""Step program, the eval's: the time the HBM would need for the least
bytes one eval has to move (``eval_bytes_floor`` of the family: the
resident test rows once, as wide as they are held, and the weights)
over the time the device was busy inside one run of the eval program
(``jit_ps_eval``, found by name in the profiler's trace, the first
device).  Percent; bandwidth-bound by construction.  Nothing where the
run was not traced, carries no eval side, or the trace holds no run of
the program (the CPU's has no device plane)."""

from chipbench import reference, trace_reduce


def read(run):
    tr, side = run.get("trace"), run.get("eval")
    if not tr or not side:
        return None
    busy = trace_reduce.busy_per_step(tr["xtrace"], side["program"],
                                      tr["window"])
    if not busy:
        return None
    peaks = trace_reduce.peaks_for(run["device_kind"])
    floor = reference.family(run["family"]).eval_bytes_floor(
        rows=side["rows"], dim=side["dim_held"])
    return 100.0 * floor / peaks["hbm_bytes_per_s"] / busy
