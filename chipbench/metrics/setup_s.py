"""Seconds from the process's start to the window's: JAX start, warm-up
on stand-ins (compiles or persistent-cache loads), the pool."""


def read(record):
    return record["setup_s"]
