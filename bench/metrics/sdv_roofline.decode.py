"""Least time of the window's packed linear calls (``work.py``, from
the layers' shapes and bit widths) over the device time of the SDV
kernels (``kernels/sdv_matmul.py``: GEMM and GEMV), in percent."""
import readers

PATTERN = r"sdv_mat(mul|vec)"


def read(r):
    return readers.roofline(r, PATTERN, readers.decode_linear_least_s(r))
