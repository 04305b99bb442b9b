"""As ``ttft_p50_ms`` (first token minus the time the request was due), the
90th percentile: the highest with ten samples beyond it at this cell's
request count."""


def read(run):
    v = run["win"].get("ttft_p90_ms")
    return None if v is None or v == float("inf") else v
