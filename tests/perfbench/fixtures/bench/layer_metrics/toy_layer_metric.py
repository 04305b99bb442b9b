def read(run):
    return 100.0 * run["hits"] / run["lookups"]
