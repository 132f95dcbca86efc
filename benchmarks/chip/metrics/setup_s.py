"""Set-up seconds: from the process's start to the window's (rendering,
loading the store, compiling from the cache, warming up)."""


def read(run):
    return run.setup_s
