"""PS exchange, phase by phase, seen from the client: the mean of the
program's ``xchg_send`` spans inside the measured ``fit`` calls, in
milliseconds: from a keyed operation's start in the native client to the
last byte of its request handed to the kernel (the frames to every
server, one after another).  The native client notes the instants and
``KVWorker`` records the span, after the call returns, under the loop's
``push`` or ``pull`` or the comm thread's ``wire``; with
``ps_xchg_await_ms`` and ``ps_xchg_recv_ms`` it covers that parent but
for the call's entry and exit.  Nothing where the program records no
such span (a program from before it did)."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "xchg_send")
