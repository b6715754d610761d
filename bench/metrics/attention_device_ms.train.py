"""Device milliseconds per traced step under the program's
``hgt_attention`` scope (HGT's scores, joint softmax and weighted pool,
both passes), read by the ``train_mesh`` driver from the step's HLO."""
UNIT = "ms"


def read(run):
    return run["records"].get("scope_ms", {}).get("hgt_attention")
