"""Host milliseconds per training step blocked on the device for the
step's loss (the ``ibmb/fit/loss_sync`` span of ``GNNTrainer.fit``) in
the traced window."""

from ibmb_bench import program_trace


def read(record):
    got = program_trace.for_record(record, __file__)
    span = got["spans"].get("ibmb/fit/loss_sync") if got else None
    per = record["counters"].get("steps")
    if not span or not per:
        return None
    return 1e3 * span["total_s"] / per
