"""Step program, the softmax step's: the time the MXU would need for the
step's useful arithmetic at its bfloat16 peak (``step_flops`` of the
family: two products of ``rows x dim x classes`` multiply-adds, counted
once) over the time the device was busy inside one run of
``jit_ps_grad_step`` in the profiler's trace.  Percent.

It counts *useful bfloat16-rate* arithmetic: a float32 product at
``highest`` costs the MXU six passes and 20 classes fill 20 of its 128
columns, neither of which is counted, so XLA's step reads a few percent
and no implementation can pass 100%.  Beside ``step_hbm_roofline`` it
says which of the two floors a later kernel is up against: on the v5e
the step's two fusions stream the shard at HBM speed and the passes hide
under them (PERF.md section 5).  Nothing where the run was not traced,
carries no such side, or the trace holds no run of the program."""

from chipbench import trace_reduce


def read(run):
    tr, side = run.get("trace"), run.get("sm")
    if not tr or not side:
        return None
    busy = trace_reduce.busy_per_step(tr["xtrace"], tr["step_program"],
                                      tr["window"])
    if not busy:
        return None
    peaks = trace_reduce.peaks_for(run["device_kind"])
    return 100.0 * side["step_flops"] / peaks["bf16_flops_per_s"] / busy
