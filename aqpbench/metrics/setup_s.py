"""Seconds from the start of the process to the start of the window."""


def read(record):
    return record["setup_s"]
