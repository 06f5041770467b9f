"""Host milliseconds per epoch spent making the epoch's batch order
(the ``ibmb/fit/schedule`` span of ``GNNTrainer.fit``) in the traced
window."""

from ibmb_bench import program_trace


def read(record):
    got = program_trace.for_record(record, __file__)
    span = got["spans"].get("ibmb/fit/schedule") if got else None
    per = record["counters"].get("epochs")
    if not span or not per:
        return None
    return 1e3 * span["total_s"] / per
