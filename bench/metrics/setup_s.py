"""Set-up seconds, from the process's start to the window's: imports,
weights, engine, warm-up, compiles or cache loads, and the store fill."""


def read(record):
    return record["setup_s"]
