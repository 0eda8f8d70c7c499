"""PS exchange, BSP, what the delay hides: the share of the comm thread's
``wire`` time (the fused push-pull of round *k*, send to reply, the wait
at the servers' barrier inside) that lies under the same worker's
``compute`` span of round *k* + 1, of those that carry ``in_flight=1``,
in percent.  A fit's last push has no next round to run under, so over
*E* rounds the share cannot pass (*E* - 1) / *E*.  Nothing where the run
carries no such side, recorded no ``wire``, or its ``compute`` spans say
nothing of what was in flight."""


def read(run):
    side = run.get("dl")
    if not side or not side["wire_s"] or not (
            side["computes_in_flight"] + side["computes_alone"]):
        return None
    return 100.0 * side["wire_under_compute_s"] / side["wire_s"]
