"""PS worker round, BSP under bounded delay: the window's wall over the
rounds each worker ran, in milliseconds: the longer of the four queued
programs and one exchange, where the lock-step round is their sum.
``ps_round_ms``'s reading, in a run that counted its delayed rounds;
nothing elsewhere."""

from chipbench.layer_metrics import ps_round_ms


def read(run):
    return ps_round_ms.read(run) if run.get("dl") else None
