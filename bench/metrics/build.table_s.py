"""Seconds the cell's table took to fit on the host and be placed on the
chip, as the program measures it (``PolyFit.build_seconds()``)."""


def read(run):
    return run.build_s
