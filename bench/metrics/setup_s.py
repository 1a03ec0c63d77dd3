"""Seconds from process start to the first timed step: loading, making
the data, compiling or loading the programs, and warming them up."""


def read(run):
    return run.setup_s
