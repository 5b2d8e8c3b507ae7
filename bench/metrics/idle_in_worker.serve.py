"""idle_in_worker.serve: share of the traced window in which the device
is idle while the server's worker is inside a ``serve.microbatch`` span,
in %: idle that the server's own host code causes, not a lack of
requests."""


def read(tr, cell, ctx):
    sp = ctx.module("spans").load(ctx)
    if sp is None or not sp.busy:
        return None
    steps = ctx.module("trace")._union(
        [(s.start, s.end) for s in sp.named("serve.microbatch")])
    if not steps:
        return None
    idle_ns = sum((e - s) - sp.busy_in(s, e) for s, e in steps)
    return 100.0 * idle_ns * 1e-9 / tr.window_s
