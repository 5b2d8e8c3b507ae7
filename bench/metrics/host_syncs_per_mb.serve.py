"""host_syncs_per_mb.serve: blocking device-to-host reads per microbatch
in the window, from the server's ``serve.host_syncs`` and
``serve.microbatches`` counters."""


def read(tr, cell, ctx):
    c = cell.win_stats["counters"]
    mbs = c.get("serve.microbatches", 0)
    if "serve.host_syncs" not in c or mbs == 0:
        return None
    return c["serve.host_syncs"] / mbs
