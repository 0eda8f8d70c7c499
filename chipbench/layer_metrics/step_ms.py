"""Device step layer: mean of the program's ``compute`` spans inside the
measured ``fit`` call (dispatch to ``block_until_ready``; what
``StepTimer`` times), in milliseconds."""


def read(run):
    span = run["window"]["spans"].get("compute")
    if not span or not span["count"]:
        return None
    return 1e3 * span["seconds"] / span["count"]
