"""PS exchange, phase by phase, seen from the client: the mean of the
program's ``xchg_await`` spans inside the measured ``fit`` calls, in
milliseconds: from the last request byte handed to the kernel to the
first reply header read: the tail of the servers' read, the lock, the
merge or apply, in lock step the wait for the slowest worker and the
release up to this worker's reply, and the reply's first bytes through
the loopback.  ``ps_xchg_send_ms`` has how the span is recorded.  Nothing
where the program records no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "xchg_await")
