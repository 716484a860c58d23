"""Least time of the window's 3x3 UltraNet stages (``work.py``, from
their shapes and bit widths) over the device time of the BSEG conv2d
kernel (``kernels/bseg_conv2d.py``), in percent."""
import readers
import work

PATTERN = r"bseg_conv2d"


def read(r):
    least = work.least_time(work.ultranet_stage_work(r.config), r.peak,
                            integer=True)[0]
    return readers.roofline(r, PATTERN, r.counters["frames"] * least)
