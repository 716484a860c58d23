"""Mean share of the bucket's slots holding a request after each
``Engine.step()`` of the window, from the engine's session table."""


def read(r):
    occ = r.counters["occupancy"]
    return 100.0 * sum(occ) / len(occ) if occ else None
