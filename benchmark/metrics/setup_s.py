"""Seconds from the start of the process to the opening of the window:
loading, building, compiling or reading the compile cache, warming up."""


def read(run):
    return run.setup_s
