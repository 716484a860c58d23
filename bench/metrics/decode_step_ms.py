"""Device time per engine iteration of the window: the traced busy
time (union of device ops) over the iterations, in ms."""


def read(r):
    n = r.counters["iterations"]
    if r.trace is None or not n or r.trace.busy_s <= 0:
        return None
    return 1e3 * r.trace.busy_s / n
