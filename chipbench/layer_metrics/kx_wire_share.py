"""PS exchange, what the keys save: the key and value bytes a keyed round
sends and receives (the window's rise of
``distlr_ps_client_bytes_total{op=pull|push}``, both directions, over its
rounds) over the bytes a dense round of the same model moves (the whole
float32 vector each way, 8 MB at D = 1M), in percent; lower is better.
Nothing where the run carries no such side."""


def read(run):
    kx = run.get("kx")
    if not kx or not kx.get("rounds") or not kx.get("dense_round_bytes"):
        return None
    moved = (kx["sent_bytes"] + kx["received_bytes"]) / kx["rounds"]
    return 100.0 * moved / kx["dense_round_bytes"]
