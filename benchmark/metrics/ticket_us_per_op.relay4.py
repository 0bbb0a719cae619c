"""Host seconds in DocumentSequencer.ticket per op in the relay's
window (the benchmark's span around the calls, 65,536 documents'
sequencers), in microseconds."""


def read(rec):
    spans = rec.get("spans_s") or {}
    if rec["loop"] != "open" or not rec.get("ops_in_window") \
            or "ticket" not in spans:
        return None
    return 1e6 * spans["ticket"] / rec["ops_in_window"]
