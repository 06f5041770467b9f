"""MiB put on the device per training step: the ``bytes`` arguments of
the ``ibmb/loader/stage`` spans in the traced window, over the steps."""

from ibmb_bench import program_trace


def read(record):
    got = program_trace.for_record(record, __file__)
    span = got["spans"].get("ibmb/loader/stage") if got else None
    nbytes = span["args"].get("bytes") if span else None
    per = record["counters"].get("steps")
    if nbytes is None or not per:
        return None
    return nbytes / 2**20 / per
