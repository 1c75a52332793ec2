"""Device self time of the sharded step's per-tick moment ``psum`` (the
``all-reduce`` ops, scope ``miss.psum``) in the traced window, per answer
completed in it; None where the trace holds no ``all-reduce``."""


def read(record):
    tr = record.get("trace")
    if not tr or not record["traced_answers"]:
        return None
    psum = [s for name, s in tr["device_ops"] if name.startswith("all-reduce")]
    if not psum:
        return None
    return 1e3 * sum(psum) / record["traced_answers"]
