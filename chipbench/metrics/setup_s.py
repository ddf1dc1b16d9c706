"""Set-up seconds: from the start of ``run.py`` to the window's opening,
imports, server start and warm-up included."""


def read(run):
    return run.setup_s
