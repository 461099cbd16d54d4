"""Seconds from the start of the process to the first timed round:
loading, data and weights, compilation (or loading it from the cache),
warm-up and the checked first rounds."""


def read(record):
    return record.setup_s
