"""Merge kernels' share of their roofline in the traced slice: the
least time of the traced rounds' bytes (roofline.py) over the device
time of the merge modules, in percent."""
from benchmark.roofline import roofline_pct

# XLA modules of the merge path on every route: the scan window and its
# donated form (merge_kernel), the egwalker walker and the chunked
# program (jax.jit of a lambda, and of ``run`` when donated), the Pallas
# window, compaction and the regrow pad
MODULES = ("jit_apply_window_impl", "jit__pingpong_impl", "jit__lambda_",
           "jit_run", "jit__pallas_call", "jit_compact", "jit_pad_capacity")


def read(rec):
    trace = rec.get("trace")
    if rec["loop"] != "open" or not trace:
        return None
    return roofline_pct([r for r in rec["traced_rounds"] if r],
                        trace["module_s"], MODULES, rec["peak"])
