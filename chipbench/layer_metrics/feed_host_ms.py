"""Input layer: the producer thread's busy time a batch inside the
measured ``fit`` call, in milliseconds: the seconds of the program's
``batch_slice`` spans (numpy slice, pad and reshape) and ``h2d`` spans
(the host's synchronous part of ``device_put``) over the batches put
(the ``h2d`` count).  Nothing where the program records no
``batch_slice``."""


def read(run):
    spans = run["window"]["spans"]
    put, sliced = spans.get("h2d"), spans.get("batch_slice")
    if not put or not sliced or not put["count"]:
        return None
    return 1e3 * (sliced["seconds"] + put["seconds"]) / put["count"]
