"""Least time of the traced decode calls over their device time, %."""
from bench import readers


def read(record):
    return readers.decode_roofline(record)
