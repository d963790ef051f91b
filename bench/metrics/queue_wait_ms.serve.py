"""Mean wait of a request in the queue, from submit() to its placing in a
slot (the engine's ``serve.queue`` spans over the window), ms."""
from bench import spans


def read(record):
    return spans.mean_ms(record, "serve.queue")
