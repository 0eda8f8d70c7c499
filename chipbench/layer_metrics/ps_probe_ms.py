"""PS worker round, the staleness probe, in milliseconds a round: the
seconds of the program's ``staleness_probe`` spans (the kStats round
trips to every server that an asynchronous worker's loop makes on its
probe connection where it stamps its weights and where it ages them, at
most every 50 ms: the probes made, not the throttled returns) over the
rounds.  Nothing where the program records no such span (lock-step
servers: a round's staleness is the round)."""


def read(run):
    spans = run["window"]["spans"]
    probe, rounds = spans.get("staleness_probe"), spans.get("round")
    if not probe or not rounds or not rounds["count"]:
        return None
    return 1e3 * probe["seconds"] / rounds["count"]
