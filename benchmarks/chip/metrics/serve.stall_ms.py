"""99th percentile, in milliseconds, over the traced window's
``serve.batch`` spans, of the time the serving thread was neither running
nor waiting on the device: the span's wall time less its thread CPU time
(``cpu_ms``) less the ``serve.session.readback`` time inside it, floored at
0 (descheduled, or waiting on a lock or a page fault)."""
import numpy as np


def read(ctx):
    batches = ((ctx.get("trace") or {}).get("span_instances") or {}).get(
        "serve.batch")
    if ctx["kind"] != "serve" or not batches:
        return None
    stall = [max(b["s"] - b["args"]["cpu_ms"] * 1e-3
                 - b["within"].get("serve.session.readback", 0.0), 0.0)
             for b in batches]
    return 1e3 * float(np.percentile(stall, 99))
