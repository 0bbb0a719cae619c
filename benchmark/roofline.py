"""The bytes a merge round must move, and its share of the roofline.

A round's least work is to read and write back the segments of every
document it touches and to read its ops. Per document with at least one
real op: 2 x its segment count x 48 B, plus its real merge ops x 48 B.
48 B is the logical record of a segment or an op (8 int32 fields and 4
property channels), a constant of the benchmark, so a change of the
program's layout cannot move the yardstick. The segment count comes
from the op stream the harness sent (the document's inserts so far),
never from the table under test. There are no FLOPs to speak of, so
bytes over the chip's HBM bandwidth bound the round: the least time.
The same count holds whichever route implements the round.
"""
from __future__ import annotations

RECORD_BYTES = 48


def round_bytes(docs: dict) -> int:
    """``docs``: {doc: (real merge ops, segments)} of one round."""
    return sum(RECORD_BYTES * (2 * segs + ops)
               for ops, segs in docs.values() if ops > 0)


def roofline_pct(rounds: list, module_s: dict, modules: tuple,
                 peak: dict) -> float | None:
    """Least time of the traced rounds over the device time of the
    merge modules in the same trace, in percent. None where the trace
    holds no merge module or no round (nothing to read)."""
    device_s = sum(v for k, v in module_s.items() if k in modules)
    if not rounds or device_s <= 0:
        return None
    least_s = sum(round_bytes(r) for r in rounds) / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
