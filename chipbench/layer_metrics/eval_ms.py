"""PS worker eval: the mean of rank 0's ``eval`` spans inside the
measured ``fit`` call, in milliseconds: one whole eval as the loop pays
it after every ``test_interval``-th round, from the pull of the weights
to the two numbers on the host, while the other workers stand at the
next round's barrier.  Nothing where the run evaluated nothing or
carries no eval side (another cell's run)."""


def eval_span_ms(run, name):
    side = run.get("eval")
    span = side["spans"].get(name) if side else None
    if not span or not span["count"]:
        return None
    return 1e3 * span["seconds"] / span["count"]


def read(run):
    return eval_span_ms(run, "eval")
