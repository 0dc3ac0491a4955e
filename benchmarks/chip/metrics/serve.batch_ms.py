"""Mean host-clock milliseconds inside ``ServeEngine.process_batch`` over
the window's batches."""


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return ctx["window"]["batch_ms"]
