"""Mean host time of one ``AQPSession.pump()`` call (the benchmark's own
span around each call) over the untraced rest of a --trace 1 window,
closed-loop cells."""


def read(record):
    if record["loop"] != "closed" or not record.get("pump_s"):
        return None
    return 1e3 * sum(record["pump_s"]) / len(record["pump_s"])
