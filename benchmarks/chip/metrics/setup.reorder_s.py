"""Seconds of the program's graph reordering in set-up (``core.reorder``):
``minhash_reorder``, and ``Graph.permute`` where the cell trains on the
reordered graph.  Host clock."""


def read(ctx):
    return ctx["setup"].get("reorder_s")
