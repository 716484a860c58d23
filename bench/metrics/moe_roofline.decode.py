"""Least time of the window's dropless expert FFN calls
(``work_granite_hybrid.expert_ffn_call``: every routed row's three
projections, the touched experts' weights at their declared width) over
the device time of the grouped-matmul kernel (``kernels/moe_gmm.py``),
in percent.  Every call is memory-bound at these sizes (a decode call
moves about 0.3 GB for 6 GFLOP), so the least time of the calls' summed
work is the sum of their least times."""
import readers
import work
import work_granite_hybrid

PATTERN = r"moe_gmm"


def read(r):
    c = r.counters
    if not c.get("moe_calls"):
        return None
    w = work_granite_hybrid.expert_ffn_call(
        r.config, c["moe_rows"], c["moe_experts_touched"])
    integer = r.config["engine"]["act_bits"] < 16
    return readers.roofline(r, PATTERN,
                            work.least_time(w, r.peak, integer=integer)[0])
