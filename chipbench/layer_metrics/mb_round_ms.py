"""PS worker round, a minibatch worker's: the mean start-to-start of a
worker's ``compute`` spans inside the measured ``fit`` calls, in
milliseconds: everything a windowed round costs the loop, the other
workers' turns on the one chip and an epoch's drain included.  From the
tracer's events of the window (as many as its buffer kept).  Nothing
where the run carries no such side."""


def read(run):
    side = run.get("mb")
    if not side or not side["rounds"]:
        return None
    return 1e3 * side["round_s"] / side["rounds"]
