def read(run):
    return None          # nothing to read: the harness leaves the metric out
