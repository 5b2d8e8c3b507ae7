"""window_pass_roofline.serve: the least time the window's microbatches
could take on the chip — one read of each chip's share of the resident
window plus V and X per microbatch at HBM bandwidth
(``bench/flops.window_pass_bytes``) — over the device time of the
request-solve programs, per chip."""

REQUEST_PROGRAMS = r"coalesced_solve|serve_local|shard_map"


def read(tr, cell, ctx):
    flops = ctx.module("flops")
    t = tr.programs_s(REQUEST_PROGRAMS)
    n_mb = tr.programs_n(REQUEST_PROGRAMS)
    c = cell.win_stats["counters"]
    served, mbs = c.get("serve.requests", 0), c.get("serve.microbatches", 0)
    if t <= 0 or n_mb == 0 or mbs == 0:
        return None
    k = served / mbs
    least = n_mb * flops.window_pass_bytes(
        cell.n, cell.m, k, chips=ctx.chips) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t
