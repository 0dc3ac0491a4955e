"""Percentage of the traced window of a serving cell in which no operation
ran on the device (``trace_reduce``)."""


def read(ctx):
    if ctx["kind"] != "serve" or ctx["trace"] is None:
        return None
    return 100.0 * ctx["trace"]["idle_share"]
