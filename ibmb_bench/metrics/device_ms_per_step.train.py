"""Device busy milliseconds per training step in the traced window."""


def read(record):
    steps = record["counters"].get("steps")
    if not record["trace"] or not steps:
        return None
    return 1e3 * record["trace"]["busy_s"] / steps
