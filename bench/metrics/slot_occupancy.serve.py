"""Mean share of the engine's decode slots occupied over the window's
ticks (the engine's own per-tick occupancy counter)."""


def read(run):
    occ = run.counts.get("slot_occupancy_mean")
    return None if occ is None else 100.0 * occ
