"""PS exchange, what the overlap failed to hide: the mean ``push`` span
that is no epoch's drain, in milliseconds: the loop blocked on the reply
to the last round's push after this round's device chain was done.
Nothing where the run carries no such side or no such span."""


def mean_push_ms(run, kind):
    side = run.get("mb")
    push = side["push"].get(kind) if side else None
    if not push or not push["count"]:
        return None
    return 1e3 * push["seconds"] / push["count"]


def read(run):
    return mean_push_ms(run, "wait")
