"""submit_p99_ms.serve: 99th percentile of the ``serve.submit`` span's
duration over the window's requests, in ms: how long a caller is held in
``AsyncSolveServer.submit`` (its lock, the batcher)."""
import numpy as np


def read(tr, cell, ctx):
    sp = ctx.module("spans").load(ctx)
    if sp is None:
        return None
    d = [s.end - s.start for s in sp.named("serve.submit")]
    return float(np.percentile(d, 99)) * 1e-6 if d else None
