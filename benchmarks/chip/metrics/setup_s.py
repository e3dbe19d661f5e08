"""setup_s: host seconds from process start to the window's start: imports,
the program's weights, warm compilation of every batch bucket (from the
persistent cache after a cell's first run), calibration, the warm-up trace."""


def read(ctx):
    return ctx["setup_s"]
