"""Device milliseconds per batch forward in ops under the ``attention``
named scope (GAT's edge logits and edge softmax) in the traced window."""

from ibmb_bench import program_trace


def read(record):
    got = program_trace.for_record(record, __file__)
    seconds = got["scopes"].get("attention") if got else None
    per = record["counters"].get("batches")
    if seconds is None or not per:
        return None
    return 1e3 * seconds / per
