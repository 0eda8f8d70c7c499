"""PS worker round, where the chips' idle time goes: of the idle seconds
of every chip that runs a worker's step, inside the traced window, the
percent that lie under that chip's own workers' ``push``, ``pull`` or
``wire`` spans and under none of their ``compute``, ``w_put``,
``grad_d2h`` or ``h2d``: the chip stood waiting for the exchange (in lock
step: the wire, the slowest worker, the release).  A piece of a gap goes
to the first of ``compute``, the link's three, the exchange's three that
any of the chip's workers is in (``trace_reduce.idle_gaps``' rule, a
plane at a time, with the PS loop's names); the device's operations are
shifted by ``ps_clock_lead_ms`` first, whose file says how and where all
four read nothing.  What ``ps_idle_exchange_share``,
``ps_idle_link_share`` and ``ps_idle_unnamed_share`` leave to 100 is
``compute`` with no operation running: the launch and the wake-up."""

from chipbench.layer_metrics.ps_clock_lead_ms import idle_share


def read(run):
    return idle_share(run, "exchange")
