"""PS worker round, where the chips' idle time goes: of the idle seconds
of every chip that runs a worker's step, inside the traced window, the
percent that lie under no ``compute``, link or exchange span of that
chip's own workers: the loop between its spans, the threads' start and
end.  More than a few percent means a span is missing.
``ps_idle_exchange_share`` has the rule, ``ps_clock_lead_ms`` the clocks
and where all four read nothing."""

from chipbench.layer_metrics.ps_clock_lead_ms import idle_share


def read(run):
    return idle_share(run, "unnamed")
