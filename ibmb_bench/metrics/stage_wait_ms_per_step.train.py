"""Host milliseconds per training step that the step waited for its
staged batch (the ``ibmb/loader/wait`` span of ``PrefetchLoader``) in
the traced window."""

from ibmb_bench import program_trace


def read(record):
    got = program_trace.for_record(record, __file__)
    span = got["spans"].get("ibmb/loader/wait") if got else None
    per = record["counters"].get("steps")
    if not span or not per:
        return None
    return 1e3 * span["total_s"] / per
