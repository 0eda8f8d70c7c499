"""PS exchange, BSP, what the delay failed to hide: the ``push`` spans
that are no drain (the loop blocked on the reply to the last round's push
after this round's gradient was done), in milliseconds a round of the
window.  Nothing where the run carries no such side or no such span."""


def read(run):
    side = run.get("dl")
    if not side or not side["rounds_counted"]:
        return None
    wait = side["push"]["wait"]
    if not wait["count"]:
        return None
    return 1e3 * wait["seconds"] / side["rounds_counted"]
