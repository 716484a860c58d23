"""Frames per second times the operations of one frame (2 x every
multiply-add, the head included) over the chip's int8 peak, percent."""
import work


def read(r):
    c = r.counters
    if not c["frames"]:
        return None
    ops = c["frames"] * work.ultranet_frame_ops(r.config)
    return 100.0 * ops / c["window_s"] / r.peak["int8_ops_per_s"]
