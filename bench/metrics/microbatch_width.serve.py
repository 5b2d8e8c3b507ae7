"""microbatch_width.serve: requests served over microbatches formed in the
window, from the server's ``serve.requests`` and ``serve.microbatches``
counters."""


def read(tr, cell, ctx):
    c = cell.win_stats["counters"]
    mb = c.get("serve.microbatches", 0)
    return c.get("serve.requests", 0) / mb if mb else None
