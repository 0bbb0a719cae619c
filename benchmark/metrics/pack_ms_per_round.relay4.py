"""Host half of the sidecar's dispatch per round on the relay (pack
and event graph over all 65,536 rows): stats["pack_s"] over
stats["rounds"] in the window, in ms."""


def read(rec):
    if rec["loop"] != "open" or not rec.get("sidecar_rounds") \
            or "pack_s" not in rec:
        return None
    return 1e3 * rec["pack_s"] / rec["sidecar_rounds"]
