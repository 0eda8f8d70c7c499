"""PS exchange, a keyed operation's way in: the mean of the program's
``xchg_enter`` spans inside the measured ``fit`` calls, in milliseconds:
from the operation's first instruction in Python (``KVWorker.push_pull``
and its siblings) to the native call's start: the frame's keys, the
reply's buffer (``np.empty_like`` maps 4 MB at a million weights), the
retry, trace and counter scopes.  ``KVWorker`` records it as the op
returns, the first of six spans that follow one another under the loop's
``push`` or ``pull`` or the comm thread's ``wire`` (``ps_xchg_send_ms``,
``ps_xchg_await_ms``, ``ps_xchg_recv_ms``, ``ps_op_wake_ms``,
``ps_op_account_ms``) and cover that parent but for its own entry and
exit.  Nothing where the program records no such span (a program from
before it did)."""

from chipbench.layer_metrics.ps_wait_ms import mean_span_ms


def read(run):
    return mean_span_ms(run, "xchg_enter")
