"""Model flops of the window's prefill and decode calls over its seconds at
the bf16 peak, %."""
from bench import readers


def read(record):
    return readers.mfu(record)
