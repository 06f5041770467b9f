"""Requests completed per micro-batching window of the async front over
the window (``ServeStats``: completed / windows)."""


def read(record):
    c = record["counters"]
    return c["completed"] / c["windows"] if c.get("windows") else None
