"""Share of the window the train loop's host spent blocked on the batch
source: ``fit``'s own ``data_wait`` spans inside the window / window."""


def read(run):
    win = run["win"]
    tr = win.get("tracer")
    if tr is None or "steps" not in win:
        return None
    return 100.0 * tr.total_s("data_wait", win["t_open"], win["t_close"]) / win["window_s"]
