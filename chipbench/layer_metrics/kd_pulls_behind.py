"""PS exchange, the keyed job's under bounded delay: how many of its own
pushes a keyed pull's reply lacked, the mean over the window's pulls:
the rise of ``distlr_ps_keyed_pull_lineage_total`` weighted by its
``behind`` label over the rise of the whole family (the program counts
it from its connection's acknowledged pushes at each pull's issue).  A
``fit`` of *E* rounds reads (*E* - 1) / *E*; 0 is the serialized
exchange, and more than 1 is not this configuration.  Nothing where the
program counted no such pull."""


def read(run):
    kd = run.get("kd")
    if not kd or not kd.get("pulls_counted"):
        return None
    return kd["pulls_behind_sum"] / kd["pulls_counted"]
