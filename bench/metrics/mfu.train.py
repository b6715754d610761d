"""Forward plus backward FLOPs (3 x forward) over the real nodes and
edges of the window's steps, over window x chips x the chip's peak."""
UNIT = "%"


def read(run):
    rec = run["records"]
    peak = run["peaks"]["flops_bf16"] * run["chips"] * rec["window_s"]
    return 100.0 * rec["train_flops"] / peak if rec["train_flops"] else None
