"""Set-up: process start to the window's first step (imports, weights,
compiling or loading every program, the checked first steps)."""


def read(run: dict):
    return run["setup_s"]
