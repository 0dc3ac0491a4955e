"""Device milliseconds per training step of the ops under an ``attention``
name scope (a GAT layer's scores and edge softmax;
``program_trace.device_scopes``), over the ``train.dispatch`` spans begun
in the traced window.  Absent where no op carries the scope."""


def read(ctx):
    tr = ctx.get("trace") or {}
    scopes, steps = tr.get("device_scopes"), (
        tr.get("harness_spans") or {}).get("train.dispatch")
    if ctx["kind"] != "train" or not scopes or not steps:
        return None
    att = [s for path, s in scopes.items() if "attention" in path.split("/")]
    if not att:
        return None
    return 1e3 * sum(att) / steps
