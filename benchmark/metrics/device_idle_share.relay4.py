"""Share of the traced slice in which no operation ran on a device of
the mesh, in percent: trace_reduce's busy time is the mean of the
device planes' busy unions, so this is the mean over the chips."""


def read(rec):
    trace = rec.get("trace")
    if rec["loop"] != "open" or not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
