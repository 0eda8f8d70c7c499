"""PS exchange, the keyed job's under bounded delay, the hidden chain: a
comm thread's ``push`` and ``pull`` spans inside the measured ``fit``
calls (the keyed push of round *k* and the keyed pull of round *k* + 2,
each sent, answered and read), in milliseconds a round: what the step
has to cover, and the round's wall once it exceeds the loop's own chain.
Nothing where the run carries no such side or the program records no
such spans."""


def read(run):
    kd = run.get("kd")
    spans = run["window"]["spans"]
    if not kd or not kd.get("rounds_per_worker") or not (
            spans.get("push") and spans.get("pull")):
        return None
    return (1e3 * (spans["push"]["seconds"] + spans["pull"]["seconds"])
            / kd["rounds_per_worker"])
