"""The open-loop generator times each request from when it was due: a
stall in the server raises the latency of every request due during it."""
import time

import numpy as np

import tiny

STALL_AT, STALL_S = 0.5, 0.4


def test_stall_raises_latency_of_every_request_due_during_it():
    seen = {}

    def patch(ctx, cell):
        seen["cell"] = cell
        batcher = cell.batcher
        inner = batcher.next_microbatch
        state = {"done": False}

        def stalling():
            t0 = getattr(cell, "t0", None)
            if t0 is not None and not state["done"] \
                    and time.perf_counter() >= t0 + STALL_AT:
                state["done"] = True
                state["start"] = time.perf_counter()
                time.sleep(STALL_S)
                state["end"] = time.perf_counter()
            return inner()

        batcher.next_microbatch = stalling
        seen["state"] = state

    res = tiny.run("table1-n2048.serve-adapt", seed=2**31 + 5, seconds=1.5,
                   patch=patch)
    cell, st = seen["cell"], seen["state"]
    assert st["done"] and res["failed"] == 0
    due = cell.t0 + cell.schedule.due
    lat = cell.last_latencies
    during = (due >= st["start"]) & (due < st["end"])
    assert during.sum() >= 10
    # each waited at least until the stall ended, counted from its due time
    assert np.all(lat[during] >= st["end"] - due[during] - 1e-3)
    # and far longer than the requests due well before the stall
    before = due < st["start"] - 0.2
    assert np.median(lat[during]) > 3 * np.median(lat[before])
