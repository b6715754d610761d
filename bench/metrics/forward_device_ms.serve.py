"""Device milliseconds per served forward, from the trace's program
events."""
UNIT = "ms"


def read(run):
    secs, runs = run["trace"].module_seconds(
        run["records"]["forward_program"])
    return 1e3 * secs / runs if runs else None
