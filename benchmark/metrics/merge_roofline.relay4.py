"""Merge kernels' share of the roofline of the mesh's chips together
in the traced slice: the traced rounds' bytes (roofline.py, every
document of every chip) over the aggregate bandwidth, the peak times
``rec["chips"]``, against the device time of the merge modules, which
the trace reduction averages over the device planes; in percent."""
from benchmark.roofline import roofline_pct

# XLA modules of the merge path on a document mesh: each program keeps
# its function's name (parallel.mesh.doc_program), so the walker is
# ``jit_run`` (donated) or ``jit__lambda``, the suffix and the scan
# route's window ``jit_apply_window_impl`` (donated: ``_pingpong_impl``),
# then compaction and the regrow pad
MODULES = ("jit_apply_window_impl", "jit__pingpong_impl", "jit__lambda",
           "jit_run", "jit_compact", "jit_pad_capacity")


def read(rec):
    trace = rec.get("trace")
    if rec["loop"] != "open" or not trace or not rec.get("chips"):
        return None
    peak = dict(rec["peak"],
                hbm_bytes_per_s=rec["peak"]["hbm_bytes_per_s"] * rec["chips"])
    return roofline_pct([r for r in rec["traced_rounds"] if r],
                        trace["module_s"], MODULES, peak)
