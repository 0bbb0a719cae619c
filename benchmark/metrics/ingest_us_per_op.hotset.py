"""Host seconds in TpuMergeSidecar.ingest per op in the window (the
benchmark's span around the calls), in microseconds."""


def read(rec):
    if rec["loop"] != "open" or not rec["ops_in_window"]:
        return None
    return 1e6 * rec["spans_s"]["ingest"] / rec["ops_in_window"]
