"""Live slots per decode call over the slots there are, %."""
from bench import readers


def read(record):
    return readers.batch_occupancy(record)
