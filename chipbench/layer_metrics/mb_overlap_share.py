"""PS exchange, what the pipeline hides: the share of the comm thread's
``wire`` time (the fused push-pull of round *k*, send to reply) that lies
under the same worker's ``w_put`` + ``compute`` + ``grad_d2h`` of round
*k* + 1, in percent.  An epoch's last push has no next round to run
under, so with *n* rounds an epoch the share cannot pass (*n* - 1) / *n*.
Nothing where the run carries no such side or recorded no ``wire``."""


def read(run):
    side = run.get("mb")
    if not side or not side["wire_s"]:
        return None
    return 100.0 * side["wire_under_chain_s"] / side["wire_s"]
