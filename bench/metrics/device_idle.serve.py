"""device_idle.serve: 1 − busy/window of the traced serving window."""


def read(tr, cell, ctx):
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
