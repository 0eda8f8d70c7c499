"""PS exchange, the hand-overs between a worker's loop and its comm
thread, in milliseconds an exchange: ``wire_handoff`` (the loop's
``submit`` to the ``wire`` span's start on the comm thread: the
executor's queue, the thread's wake-up, the interpreter) and
``reply_wake`` (inside the loop's ``push``, from the later of that
span's start and the ``wire`` span's end to ``Future.result()``
returned: a reply that was there, waiting for the loop to run), their
seconds over the ``wire`` spans' count.  ``ps_wire_ms`` and this are
what a push that nothing hides costs the loop.  Nothing where the
program records no such spans (a lock-step job has no comm thread)."""


def read(run):
    spans = run["window"]["spans"]
    wire = spans.get("wire")
    sides = [spans[n] for n in ("wire_handoff", "reply_wake") if n in spans]
    if not sides or not wire or not wire["count"]:
        return None
    return 1e3 * sum(s["seconds"] for s in sides) / wire["count"]
