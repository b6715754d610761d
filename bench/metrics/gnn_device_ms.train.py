"""Device milliseconds per traced step under the program's ``gnn``
scope (message passing, both passes), read by the ``train_mesh``
driver from the step's HLO."""
UNIT = "ms"


def read(run):
    return run["records"].get("scope_ms", {}).get("gnn")
