"""``tokens_per_s`` times the model operations of a token (mean of
``work_granite_hybrid.model_ops_per_token`` at each emitted token's
context) over the chip's int8 peak, in percent: the yardstick of
``mfu.decode`` for the Mamba2/attention/MoE stage."""
import work_granite_hybrid


def read(r):
    ctx = r.counters["contexts"]
    if not ctx:
        return None
    ops = sum(work_granite_hybrid.model_ops_per_token(r.config, c)
              for c in ctx) / len(ctx)
    return 100.0 * r.end_to_end["tokens_per_s"] * ops \
        / r.peak["int8_ops_per_s"]
