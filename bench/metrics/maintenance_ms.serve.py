"""maintenance_ms.serve: device time of the curvature maintenance programs
(folds, refreshes, audits) per microbatch of the traced window, in ms."""

MAINTENANCE_PROGRAMS = r"fold_window|sharded_fold|refresh|audit|cholesky"


def read(tr, cell, ctx):
    mbs = cell.win_stats["counters"].get("serve.microbatches", 0)
    if mbs == 0:
        return None
    return tr.programs_s(MAINTENANCE_PROGRAMS) / mbs * 1e3
