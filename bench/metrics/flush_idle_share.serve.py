"""Share of the traced window in which the device runs no operation while
the host is inside a ``serve.flush`` span, %."""
from bench import spans


def read(record):
    return spans.flush_idle_share(record)
