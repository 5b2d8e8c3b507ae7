"""solve_roofline.solve: the least time one Algorithm-1 solve could take
on this chip, max(FLOPs / bf16 peak, two reads of S / HBM bandwidth)
(``bench/flops.py``), over the mean device time of the solve program."""


def read(tr, cell, ctx):
    flops = ctx.module("flops")
    n_calls = tr.programs_n(r"^jit_chol_solve_call$")
    if n_calls == 0:
        return None
    t = tr.programs_s(r"^jit_chol_solve_call$") / n_calls
    pk = ctx.peaks
    least = max(flops.chol_solve_flops(cell.n, cell.m) / pk["bf16_flops_per_s"],
                flops.chol_solve_bytes(cell.n, cell.m) / pk["hbm_bytes_per_s"])
    return 100.0 * least / t
