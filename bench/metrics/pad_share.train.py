"""Real nodes and edges over padded capacity, summed over the window's
batches, counted on the host."""
UNIT = "%"


def read(run):
    padded = run["records"]["padded"]
    cap = sum(c for _, c in padded)
    return 100.0 * sum(r for r, _ in padded) / cap if cap else None
