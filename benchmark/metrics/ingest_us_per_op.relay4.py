"""Host seconds in TpuMergeSidecar.ingest per op in the relay's window
(the benchmark's span around the calls), in microseconds."""


def read(rec):
    spans = rec.get("spans_s") or {}
    if rec["loop"] != "open" or not rec.get("ops_in_window") \
            or "ingest" not in spans:
        return None
    return 1e6 * spans["ingest"] / rec["ops_in_window"]
