"""PS worker round, BSP: the window's wall over the rounds each worker
ran, in milliseconds: the slowest worker's step, the exchange nothing
overlaps and the servers' release, every round.  ``ps_round_ms``'s
reading, in a run that counted its servers' rounds; nothing elsewhere."""

from chipbench.layer_metrics import ps_round_ms


def read(run):
    return ps_round_ms.read(run) if run.get("bsp") else None
