"""Lane-pool scheduling rounds (growth of ``stats()["pool"]["ticks"]``)
per answer, over the untraced rest of a --trace 1 window, closed-loop cells."""


def read(record):
    if record["loop"] != "closed" or not record.get("rest_answers"):
        return None
    return record["pool_ticks"] / record["rest_answers"]
