"""Host wait on the device at the sidecar's settle per round: its
stats["settle_s"] over stats["rounds"] in the window, in ms."""


def read(rec):
    if rec["loop"] != "open" or not rec["sidecar_rounds"]:
        return None
    return 1e3 * rec["settle_s"] / rec["sidecar_rounds"]
