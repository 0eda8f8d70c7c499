"""Input layer: the mean of the program's ``h2d_wait`` spans inside the
measured ``fit`` call, in milliseconds: how long a step's batch, already
handed over by the producer, still took to land on the device.  Nothing
where the program records no such span."""


def read(run):
    span = run["window"]["spans"].get("h2d_wait")
    if not span or not span["count"]:
        return None
    return 1e3 * span["seconds"] / span["count"]
