"""Input layer: the share of the measured ``fit`` call that its loop
spent waiting for the next device-ready batch (the program's
``data_load`` spans), in percent of the call's wall."""


def read(run):
    win = run["window"]
    span = win["spans"].get("data_load")
    if span is None or win["wall_s"] <= 0:
        return None
    return 100.0 * span["seconds"] / win["wall_s"]
