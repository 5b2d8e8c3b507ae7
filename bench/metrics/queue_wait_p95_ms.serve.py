"""queue_wait_p95_ms.serve: 95th percentile over the window's requests of
the time from the start of a request's ``serve.submit`` span to the start
of the ``serve.dispatch`` span whose ``uids`` hold it, in ms: the wait the
server's ``serve.queue_wait_s`` histogram buckets, unbucketed."""
import numpy as np


def read(tr, cell, ctx):
    spans = ctx.module("spans")
    sp = spans.load(ctx)
    if sp is None:
        return None
    submitted = {int(s.args["uid"]): s.start
                 for s in sp.named("serve.submit") if "uid" in s.args}
    waits = [d.start - submitted[u] for d in sp.named("serve.dispatch")
             for u in spans.uids(d) if u in submitted]
    return float(np.percentile(waits, 95)) * 1e-6 if waits else None
