"""PS worker round, the loop's own Python, in milliseconds a round: the
self seconds of the program's ``round`` spans (a round's body less the
named spans inside it: the step timer, the exchange's stamp and age, the
executor's ``submit``, the calls between) and of its ``epoch_end`` spans
(an epoch's end less its drain, eval and checkpoint: the jit probes, the
device-bytes sample, the interval tests), over the rounds.  In a
whole-shard cell every round ends an epoch.  Nothing where the program
records no ``round`` span."""


def read(run):
    spans = run["window"]["spans"]
    rounds = spans.get("round")
    if not rounds or not rounds["count"]:
        return None
    own = rounds["self_seconds"] + spans.get(
        "epoch_end", {"self_seconds": 0.0})["self_seconds"]
    return 1e3 * own / rounds["count"]
