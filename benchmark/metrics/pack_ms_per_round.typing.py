"""Host half of the sidecar's dispatch (pack, event graph) per round:
its stats["pack_s"] over stats["rounds"] in the window, in ms."""


def read(rec):
    if rec["loop"] != "open" or not rec["sidecar_rounds"]:
        return None
    return 1e3 * rec["pack_s"] / rec["sidecar_rounds"]
