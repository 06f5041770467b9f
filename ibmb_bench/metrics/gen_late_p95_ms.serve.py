"""95th percentile of how late the load generator sent its requests, in
ms, by the host clock: a starved generator shows here, not as a fast
server."""

import numpy as np


def read(record):
    late = record["counters"].get("late_ms")
    return float(np.percentile(late, 95)) if late else None
