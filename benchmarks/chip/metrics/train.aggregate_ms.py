"""Device milliseconds per training step of the ops under an ``aggregate``
name scope (``program_trace.device_scopes``), over the ``train.dispatch``
spans begun in the traced window.  The fused layer kernel, which aggregates
and updates in one launch, counts whole."""


def read(ctx):
    tr = ctx.get("trace") or {}
    scopes, steps = tr.get("device_scopes"), (
        tr.get("harness_spans") or {}).get("train.dispatch")
    if ctx["kind"] != "train" or not scopes or not steps:
        return None
    agg = sum(s for path, s in scopes.items()
              if "aggregate" in path.split("/"))
    return 1e3 * agg / steps
