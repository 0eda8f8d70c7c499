"""PS worker eval: rank 0's ``eval`` spans as a share of the measured
call's wall, in percent: what the launcher's ``TEST_INTERVAL`` costs the
job, since in lock step every worker waits while rank 0 evaluates.
Nothing where the run carries no eval side or recorded no eval."""


def read(run):
    side = run.get("eval")
    span = side["spans"].get("eval") if side else None
    if not span or not span["count"] or side["wall_s"] <= 0:
        return None
    return 100.0 * span["seconds"] / side["wall_s"]
