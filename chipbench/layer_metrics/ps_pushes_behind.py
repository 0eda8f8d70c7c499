"""PS exchange: the mean, over the window, of the program's
``distlr_train_staleness_pushes`` observations: how many pushes the
group applied between a worker's weights arriving and the gradient it
computed on them leaving.  Reported, not judged: the reference bounds it
by nothing.  Nothing where the program observed none."""


def read(run):
    ps = run.get("ps")
    if not ps or not ps.get("pushes_behind_count"):
        return None
    return ps["pushes_behind_sum"] / ps["pushes_behind_count"]
