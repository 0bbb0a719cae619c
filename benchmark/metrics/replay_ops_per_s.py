"""Ops applied and settled in the window over its seconds (closed-loop
replay cells)."""


def read(rec):
    if rec["loop"] != "closed":
        return None
    return rec["ops_in_window"] / rec["window_s"]
