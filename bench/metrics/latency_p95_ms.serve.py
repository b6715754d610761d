"""95th percentile over every request due in the window, each timed
from its due time to its answer; a failed or unanswered request counts
with its whole wait."""
UNIT = "ms"


def read(run):
    return run["records"]["latency_p95_ms"]
