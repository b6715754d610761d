"""Device milliseconds of the train step program per traced step, from
the trace's program events."""
UNIT = "ms"


def read(run):
    rec = run["records"]
    secs, runs = run["trace"].module_seconds(rec["step_program"])
    if not runs or not rec["traced_steps"]:
        return None
    return 1e3 * secs / rec["traced_steps"]
