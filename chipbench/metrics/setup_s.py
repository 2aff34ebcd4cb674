"""Seconds from process start to the window's first call: imports,
device start, traffic set-up, and one call of the window's shape that
compiles or loads every executable."""


def read(run):
    return run.setup_s
