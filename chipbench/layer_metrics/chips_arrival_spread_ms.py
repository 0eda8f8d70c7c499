"""PS server barrier, a worker to a chip: milliseconds between a round's
first push arriving at a server and its last, over the window and all
servers (the rise of kStats' ``sync_spread_seconds`` over the rise of
``sync_rounds``): with a chip a worker the four arrive together, where
one chip ran their programs one after another.
``bsp_arrival_spread_ms``'s reading in a run laid out a worker to a
chip; nothing elsewhere."""

from chipbench.layer_metrics import bsp_arrival_spread_ms


def read(run):
    return bsp_arrival_spread_ms.read(run) if run.get("on_chips") else None
