"""PS exchange, a keyed operation's own bookkeeping: the mean of the
program's ``xchg_account`` spans inside the measured ``fit`` calls, in
milliseconds: from Python running again after the native call to the
operation's return: the reply checked, the native client's instants and
carriers read, the payload, byte and latency counters, the scopes'
exits and the recording of the op's six spans; the tracing's own share
of an exchange is in here.  Nothing where the program records no such
span."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "xchg_account")
