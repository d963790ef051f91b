"""Share of the traced window in which no operation ran on the device, %."""
from bench import readers


def read(record):
    return readers.idle_share(record)
