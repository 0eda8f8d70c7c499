"""PS exchange, the keyed job's under bounded delay, what the delay
hides: the share of the comm threads' ``push`` and ``pull`` seconds
(``kd_wire_ms``) that lies under the same worker's ``w_put``,
``compute`` or ``grad_d2h`` span, over the tracer's events of the
window, in percent.  A fit's first pull and its last push have no step
to run under.  Nothing where the run carries no such side or recorded no
such spans."""


def read(run):
    kd = run.get("kd")
    if not kd or not kd.get("wire_s"):
        return None
    return 100.0 * kd["wire_under_chain_s"] / kd["wire_s"]
