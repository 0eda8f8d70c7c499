"""PS exchange, the keyed job's under bounded delay, what the delay
failed to hide: the program's ``exchange_wait`` spans inside the measured
``fit`` calls, in milliseconds a round: the loop's hand-over of round
*k*'s push to its comm thread and its wait for the reply to the pull of
round *k* + 1, all of the exchange the loop still sees.  Nothing where
the run carries no such side or the program records no such span."""


def read(run):
    kd = run.get("kd")
    span = run["window"]["spans"].get("exchange_wait")
    if not kd or not kd.get("rounds_per_worker") or not span:
        return None
    return 1e3 * span["seconds"] / kd["rounds_per_worker"]
