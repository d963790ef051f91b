"""Mean device time of one decode-program call in the traced window, ms."""
from bench import readers


def read(record):
    return readers.program_ms(record, "decode")
