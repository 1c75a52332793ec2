"""Answers completed inside the window, per second of the window."""


def read(record):
    return record["answers"] / record["window_s"]
