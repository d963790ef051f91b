"""Mean host time of one entry's flush into the host store (the engine's
``serve.flush`` spans over the window: the copy's blocking part, insert,
evictions), ms."""
from bench import spans


def read(record):
    return spans.mean_ms(record, "serve.flush")
