"""Step program: the time the HBM would need for the least bytes a step
has to move (``trace_reduce.step_bytes_floor``), over the time the
device was busy inside one run of the step program in the profiler's
trace.  Percent; bandwidth-bound by construction, the step does next to
no arithmetic per byte."""

from chipbench import trace_reduce


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    busy = trace_reduce.busy_per_step(tr["xtrace"], tr["step_program"],
                                      tr["window"])
    if not busy:
        return None
    peaks = trace_reduce.peaks_for(run["device_kind"])
    floor = trace_reduce.step_bytes_floor(run["family"], **run["step"])
    return 100.0 * floor / peaks["hbm_bytes_per_s"] / busy
