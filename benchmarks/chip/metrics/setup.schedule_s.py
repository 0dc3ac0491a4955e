"""Seconds of ``exec.autotune_forward`` in set-up: a race of schedules on
a checkout's first run, a cached verdict and the plan build after it.  Host
clock; absent where the cell schedules nothing."""


def read(ctx):
    return ctx["setup"].get("schedule_s")
