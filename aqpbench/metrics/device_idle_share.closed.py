"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window), closed-loop cells."""


def read(record):
    tr = record.get("trace")
    if record["loop"] != "closed" or not tr:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
