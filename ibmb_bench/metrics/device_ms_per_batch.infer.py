"""Device busy milliseconds per batch forward in the traced window."""


def read(record):
    batches = record["counters"].get("batches")
    if not record["trace"] or not batches:
        return None
    return 1e3 * record["trace"]["busy_s"] / batches
