"""Mean requests per served forward over the largest batch, from
`GNNServer.stats` before and after the window."""
UNIT = "%"


def read(run):
    rec = run["records"]
    if not rec["batches"]:
        return None
    return 100.0 * rec["served"] / (rec["batches"] * rec["max_batch"])
