"""release_hold_ms.serve: mean over the window's microbatches of the time
from the end of ``serve.wait`` (the answers are on the device) to the end
of the next ``serve.release`` on the worker's thread (the callers may take
them), in ms: the maintenance that answered requests wait for."""
import bisect


def read(tr, cell, ctx):
    sp = ctx.module("spans").load(ctx)
    if sp is None:
        return None
    releases = {}
    for r in sp.named("serve.release"):
        releases.setdefault(r.line, []).append(r)
    holds = []
    for w in sp.named("serve.wait"):
        rs = releases.get(w.line, [])
        i = bisect.bisect_left([r.start for r in rs], w.end)
        if i < len(rs):
            holds.append(rs[i].end - w.end)
    return sum(holds) / len(holds) * 1e-6 if holds else None
