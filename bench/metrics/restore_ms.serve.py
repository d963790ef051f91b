"""Mean host time of one restore from the host store into a slot (the
engine's ``serve.restore`` spans over the window), ms."""
from bench import spans


def read(record):
    return spans.mean_ms(record, "serve.restore")
