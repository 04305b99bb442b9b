"""How late the load generator ran: send time minus due time, 95th
percentile over the window's requests. A starved generator must not be read
as a fast server."""


def read(run):
    return run["win"].get("generator_lateness_p95_ms")
