"""Share of the traced part of the window in which no operation ran on
the chip, in %: 1 - (union of op intervals) / (traced seconds)."""


def read(run):
    return run.idle_share_pct()
