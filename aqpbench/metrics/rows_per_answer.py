"""Rows the session sampled in the window (the growth of
``AQPSession.rows_touched``, every route) per answer completed in it."""


def read(record):
    return record["rows_touched"] / record["answers"] if record["answers"] \
        else None
