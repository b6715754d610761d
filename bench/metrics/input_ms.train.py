"""Host milliseconds per window step spent in the provider's iterator
(sampling and merge/pad), read by the benchmark's provider wrapper."""
UNIT = "ms"


def read(run):
    secs = run["records"]["input_s"]
    return 1e3 * sum(secs) / len(secs) if secs else None
