"""95th percentile, in milliseconds, of how long a request waited in the
``MicroBatcher``: the program's ``serve.queue_seconds`` histogram (batch
start minus submit, host clock) over the traced window."""


def read(ctx):
    hist = ((ctx.get("registry") or {}).get("histograms") or {}).get(
        "serve.queue_seconds")
    if ctx["kind"] != "serve" or not hist or not hist["count"]:
        return None
    return 1e3 * hist["p95"]
