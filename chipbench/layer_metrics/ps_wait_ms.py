"""PS exchange, seen from the loop: the mean of the program's ``push``
spans inside the measured ``fit`` calls, in milliseconds: how long a
worker's loop stood blocked on its push (the fused push-pull's reply,
where the comm thread carries it): the part of the exchange that no
compute hid.  Nothing where the program records no such span."""


def mean_span_ms(run, name):
    span = run["window"]["spans"].get(name)
    if not span or not span["count"]:
        return None
    return 1e3 * span["seconds"] / span["count"]


def read(run):
    return mean_span_ms(run, "push")
