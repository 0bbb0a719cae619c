"""Share of the traced slice in which no operation ran on the device,
in percent (trace_reduce: 1 - busy union / slice)."""


def read(rec):
    trace = rec.get("trace")
    if rec["loop"] != "open" or not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
