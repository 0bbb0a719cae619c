"""Host time of the round's packed buffers' device_put, split by
document over the mesh: the sidecar's stats["place_s"] over its
stats["rounds"] in the window, in ms."""


def read(rec):
    st = rec.get("sidecar_stats")
    if rec["loop"] != "open" or not st or "place_s" not in st \
            or not st.get("rounds"):
        return None
    return 1e3 * st["place_s"] / st["rounds"]
