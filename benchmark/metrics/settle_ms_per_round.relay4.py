"""Host wait on the devices at the sidecar's settle per round (on a
mesh, the read of one overflow flag reduced over the chips): its
stats["settle_s"] over stats["rounds"] in the window, in ms."""


def read(rec):
    if rec["loop"] != "open" or not rec["sidecar_rounds"]:
        return None
    return 1e3 * rec["settle_s"] / rec["sidecar_rounds"]
