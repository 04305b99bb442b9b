"""Mean share of the decode batch's rows doing useful work over the window:
``ServingStats.occupancy_sum / steps`` (the engine's own counters)."""


def read(run):
    c = run["win"].get("counters")
    if not c or c.get("occupancy") is None:
        return None
    return 100.0 * c["occupancy"]
