"""Device time of the ESTIMATE Pallas kernels (poisson_bootstrap and the
segment bootstrap) in the traced window, per answer completed in it."""


def read(record):
    tr = record.get("trace")
    if not tr or not tr["estimate_kernel_s"] or not record["traced_answers"]:
        return None
    return 1e3 * tr["estimate_kernel_s"] / record["traced_answers"]
