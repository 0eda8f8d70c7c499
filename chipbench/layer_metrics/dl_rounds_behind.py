"""PS exchange, BSP under bounded delay: how many rounds' updates the
weights under a round's gradient lacked, the mean over the window's
rounds: the rise of ``distlr_ps_delayed_rounds_total{behind="1"}`` over
the rise of the whole family.  A ``fit`` of *E* rounds reads (*E* - 1) /
*E*; 0 is lock step, and more than 1 is not this configuration.  Nothing
where the program counted no such round."""


def read(run):
    side = run.get("dl")
    if not side or not side["rounds_counted"]:
        return None
    return side["rounds_behind_sum"] / side["rounds_counted"]
