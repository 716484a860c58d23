"""Mean over the window's MoE layer calls of the busiest expert's rows
over an even share (routed rows / experts): 1.0 is even routing, and
the grouped matmul's time follows the busiest expert (program counter:
``EngineMetrics.moe_load_peak`` / ``moe_calls``)."""


def read(r):
    c = r.counters
    if not c.get("moe_calls"):
        return None
    return c["moe_load_peak"] / c["moe_calls"]
