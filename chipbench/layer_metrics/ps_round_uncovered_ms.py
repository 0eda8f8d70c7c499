"""PS worker round, what no span covers, in milliseconds a round: the
window's wall over a worker's rounds, less the seconds of its
``round``, ``data_load`` and ``epoch_end`` spans over the same rounds.
The three follow one another on the loop's thread, so what is left is
their own entries and exits, the loop's ``for``, a ``fit``'s start and
end (once a window) and, where workers finish apart, the wait of the
window's wall for the last of them.  Nothing where the program records
no ``round`` span."""


def read(run):
    window = run["window"]
    spans = window["spans"]
    rounds = spans.get("round")
    if not rounds or not rounds["count"]:
        return None
    covered = sum(spans[n]["seconds"]
                  for n in ("round", "data_load", "epoch_end") if n in spans)
    return 1e3 * (window["wall_s"] - covered) / rounds["count"]
