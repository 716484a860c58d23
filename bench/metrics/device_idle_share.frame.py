"""Percent of the traced window with no op on the device (frame stream)."""
import readers


def read(r):
    return readers.idle_share(r)
