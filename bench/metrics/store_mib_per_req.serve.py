"""MiB moved between device and host store per finished request."""
from bench import readers


def read(record):
    return readers.store_mib_per_request(record)
