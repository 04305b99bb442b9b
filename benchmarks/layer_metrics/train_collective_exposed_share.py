"""Share of the traced window in which a collective operation ran on a
device while no other operation did (the exchange the step could not hide);
worst device. Nothing to read on one chip."""


def read(run):
    red = run.get("trace")
    if not red or run["cell"].chips < 2 or "steps" not in run["win"]:
        return None
    if red["collective_s"] <= 0:
        return None
    return 100.0 * red["collective_exposed_s"] / red["collective_window_s"]
