"""Share of the checked answers that Q_rel refined exactly (the mean of
``Answer.refined``), in %."""


def read(run):
    r = run.record.refined
    return float(r.mean() * 100.0) if len(r) else None
