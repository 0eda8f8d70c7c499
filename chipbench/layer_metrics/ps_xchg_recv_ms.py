"""PS exchange, phase by phase, seen from the client: the mean of the
program's ``xchg_recv`` spans inside the measured ``fit`` calls, in
milliseconds: from the first reply header read to the last value read:
the replies' values out of the sockets into the caller's array, server
after server.  ``ps_xchg_send_ms`` has how the span is recorded.  Nothing
where the program records no such span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "xchg_recv")
