"""PS exchange, a keyed operation's way back to the interpreter: the mean
of the program's ``xchg_wake`` spans inside the measured ``fit`` calls,
in milliseconds: from the last reply value in the caller's buffer (the
native client's fourth instant) to the first ``time.perf_counter()``
Python reads after the native call: the call's exit and the wait to hold
the interpreter again, which another worker's loop may have taken
meanwhile.  Four workers released by one barrier queue here.  Nothing
where the program records no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "xchg_wake")
