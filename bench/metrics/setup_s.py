"""Set-up: from process start to the window's start (imports, weights,
engine and kernel plan, gateway boot, warm-up of every program)."""


def read(run):
    return run.setup_s
