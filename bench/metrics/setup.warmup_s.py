"""Seconds of warm-up: ``ServingEngine.warmup`` AOT-compiling (or loading
from the persistent cache) the bucket ladder, then the traffic's own
shapes served once."""


def read(run):
    return run.warmup_s
