"""Output tokens made in the closed-loop window over its seconds, the
device caught up at both ends."""


def read(record):
    if record["loop"] != "closed":
        return None
    return record["tokens"] / record["window_s"]
