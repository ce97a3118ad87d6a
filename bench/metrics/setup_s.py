"""Set-up seconds: process start to the first scheduled request — data,
index construction, placement on the chip, AOT warm-up of the bucket
ladder and of the traffic's own shapes."""


def read(run):
    return run.setup_s
