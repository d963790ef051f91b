"""Median latency, due time to done(), over every request due in the
window, ms."""
from bench import readers


def read(record):
    return readers.percentile_ms(record, 50)
