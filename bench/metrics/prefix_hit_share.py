"""Admissions served by a host-store restore over admissions in the window,
%."""
from bench import readers


def read(record):
    return readers.prefix_hit_share(record)
