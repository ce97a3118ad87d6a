"""Range queries answered per second: every query of every answered
request, over the time from the window's start to the last answer."""


def read(run):
    rec = run.record
    done = int(rec.sizes[rec.ok].sum())
    return done / rec.span_s if done and rec.span_s > 0 else None
