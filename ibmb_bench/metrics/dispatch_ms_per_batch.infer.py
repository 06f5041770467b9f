"""Host milliseconds per batch forward spent in the jitted call, which
enqueues the host batch's transfer and the forward (the
``ibmb/engine/forward`` span of ``GNNInferenceEngine``), in the traced
window."""

from ibmb_bench import program_trace


def read(record):
    got = program_trace.for_record(record, __file__)
    span = got["spans"].get("ibmb/engine/forward") if got else None
    per = record["counters"].get("batches")
    if not span or not per:
        return None
    return 1e3 * span["total_s"] / per
