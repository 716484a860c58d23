"""Percent of the traced window with no op on the device (decode cells)."""
import readers


def read(r):
    return readers.idle_share(r)
