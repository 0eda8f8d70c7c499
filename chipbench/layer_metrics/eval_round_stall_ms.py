"""PS server barrier, what an eval costs the workers that do not run it:
the mean ``push`` span of ranks 1 and up on the rounds that follow one of
rank 0's evals (their fused push-pull waits at the barrier until rank 0
has evaluated, computed and pushed) less their mean on every other
round, in milliseconds.  Nothing where the run carries no eval side or
either kind of round is missing."""


def read(run):
    side = run.get("eval")
    pushes = side.get("pushes") if side else None
    if not pushes or not all(v["count"] for v in pushes.values()):
        return None
    mean = {k: v["seconds"] / v["count"] for k, v in pushes.items()}
    return 1e3 * (mean["after"] - mean["other"])
