"""Load skew over the mesh: the most real merge ops one device applied
in the window over the mean per device (stats["shard_real_ops"]); 1
is even."""


def read(rec):
    st = rec.get("sidecar_stats")
    ops = (st or {}).get("shard_real_ops")
    if rec["loop"] != "open" or not ops or sum(ops) <= 0:
        return None
    return max(ops) * len(ops) / sum(ops)
