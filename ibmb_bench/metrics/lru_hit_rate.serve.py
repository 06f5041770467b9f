"""Share of routed batch lookups answered from the engine's LRU over the
window, in %: ``lru_hits / (lru_hits + batch_runs)`` of
``GNNInferenceEngine.stats``."""


def read(record):
    c = record["counters"]
    served = c.get("lru_hits", 0) + c.get("batch_runs", 0)
    return 100.0 * c["lru_hits"] / served if served else None
