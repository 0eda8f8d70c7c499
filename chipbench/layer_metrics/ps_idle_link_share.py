"""PS worker round, where the chips' idle time goes: of the idle seconds
of every chip that runs a worker's step, inside the traced window, the
percent that lie under that chip's own workers' ``w_put``, ``grad_d2h``
or ``h2d`` spans and under none of their ``compute``: the chip stood
waiting for the link (weights to the device, the gradient read back).
``ps_idle_exchange_share`` has the rule, ``ps_clock_lead_ms`` the clocks
and where all four read nothing."""

from chipbench.layer_metrics.ps_clock_lead_ms import idle_share


def read(run):
    return idle_share(run, "link")
