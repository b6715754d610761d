"""Forward FLOPs of the answered requests' real subgraphs over window x
chips x the chip's peak."""
UNIT = "%"


def read(run):
    rec = run["records"]
    peak = run["peaks"]["flops_bf16"] * run["chips"] * rec["window_s"]
    return 100.0 * rec["serve_flops"] / peak if rec["serve_flops"] else None
