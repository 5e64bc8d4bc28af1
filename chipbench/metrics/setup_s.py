"""Set-up: process start to the window's opening (loading, compiling or
loading compiled programs, warming every shape, the pre-roll)."""


def read(run):
    return run.setup_s
