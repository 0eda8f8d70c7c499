"""PS worker round, a worker to a chip: the window's wall over the
rounds each worker ran, in milliseconds: one step (the chips compute at
the same time), the exchange nothing overlaps and the servers' release,
every round.  ``ps_round_ms``'s reading in a run laid out a worker to a
chip; nothing elsewhere."""

from chipbench.layer_metrics import ps_round_ms


def read(run):
    return ps_round_ms.read(run) if run.get("on_chips") else None
