"""95th percentile of how late the load generator handed requests to the
server, against their scheduled arrivals, in milliseconds."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return ctx["window"]["gen_late_p95_ms"]
