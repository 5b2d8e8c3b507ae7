"""queue_wait_ms.serve: 95th percentile of the server's own
``serve.queue_wait_s`` histogram (submit to dispatch) over the window's
requests, in ms. The histogram's buckets are factor-2 wide: the value is
the upper bound of the bucket that holds the 95th percentile."""


def quantile(hist, q):
    total = sum(hist["counts"])
    if total <= 0:
        return None
    acc = 0
    for i, c in enumerate(hist["counts"]):
        acc += c
        if acc >= q * total and c > 0:
            return hist["bounds"][i] if i < len(hist["bounds"]) \
                else float("inf")
    return float("inf")


def read(tr, cell, ctx):
    h = cell.win_stats["histograms"].get("serve.queue_wait_s")
    if h is None:
        return None
    v = quantile(h, 0.95)
    return None if v is None else v * 1e3
