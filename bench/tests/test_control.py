"""The control — the plain reference in the precision below the
configuration's, put in the program's place — must come out not correct
under each cell's own limit. Here at a tiny size on the CPU; the chip
readings at the cells' sizes are in PERF.md."""
import numpy as np
import pytest

import tiny

SEEDS = [2**31 + 11, 2**31 + 12, 2**31 + 13]


def _cell(workload, seed, seconds=0.5):
    R = tiny.harness()
    ctx = R.Ctx(tiny.spec(), workload, seed, seconds, False)
    R.check_device(ctx, require_tpu=False)
    drv = R.load_module(R.BENCH / "drivers" / f"{ctx.traffic['driver']}.py",
                        "bench_driver_" + ctx.traffic["driver"])
    return ctx, drv.setup(ctx)


@pytest.mark.parametrize("seed", SEEDS)
def test_solve_control_fails(seed):
    ctx, cell = _cell("table1-n2048.solve", seed)
    cell.window(0.3)
    cell.release()
    idx = cell.sample()
    X = np.stack([cell.kept[i] for i in idx], 1).astype(np.float64)
    Xr = cell.answers(idx)
    limit = ctx.limits["solve_rel_err"]
    assert cell.readings(X, Xr)["solve_rel_err"] <= limit
    assert cell.readings(cell.answers(idx, "high"), Xr)["solve_rel_err"] \
        > limit


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_fails(seed):
    ctx, cell = _cell("table1-n2048.serve-adapt", seed)
    cell.window(0.5)
    cell.release()
    idx = cell.sample_idx
    Xr = cell.answers(idx)
    X = {i: cell.kept[i].astype(np.float64) for i in idx}
    limit = ctx.limits["answer_rel_err"]
    assert cell.readings(X, Xr)["answer_rel_err"] <= limit
    assert cell.readings(cell.answers(idx, "high"), Xr)["answer_rel_err"] \
        > limit
