"""Roofline analysis from the compiled dry-run (TPU v5e targets).

Terms per (arch × shape × mesh), all in seconds-per-step per chip:

    compute    = FLOPs            / (chips × 197e12 bf16 FLOP/s)
    memory     = HBM bytes        / (chips × 819e9  B/s)
    collective = collective bytes / (chips × 4 links × 50e9 B/s)

Methodology (documented in DESIGN.md §7): `compiled.cost_analysis()` counts
while-loop bodies ONCE (measured ratio 1.0 on this jax), so HLO-derived
FLOPs under-report scanned layers.  We therefore compute FLOPs/bytes
ANALYTICALLY from the architecture math (validated against cost_analysis on
small unrolled configs in tests/test_roofline.py) and take collective bytes
from the partitioned HLO, re-scaled by the known scan trip counts (layers ×
microbatches for in-body collectives).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from pathlib import Path

from repro.configs.base import ArchConfig, SHAPES, ShapeConfig
from repro.models.registry import get_config, runnable_cells

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link
ICI_LINKS = 4                # v5e 2D torus: 4 links/chip


# ---------------------------------------------------------------------------
# Analytic FLOPs / bytes per step (whole job, later divided by chips)
# ---------------------------------------------------------------------------

def _attention_flops(cfg: ArchConfig, tokens: int, kv_len: int,
                     causal_half: bool) -> float:
    """QK^T + PV for all layers; causal_half halves the quadratic term."""
    hd = cfg.resolved_head_dim
    layers = cfg.num_layers if cfg.family != "audio" else 0
    quad = 2 * 2 * tokens * kv_len * cfg.n_heads * hd
    if causal_half:
        quad /= 2
    return layers * quad


def step_flops(cfg: ArchConfig, shape: ShapeConfig, *,
               causal_skip: bool = False) -> dict:
    """Returns dict with model_flops (6ND ideal) and hlo-equivalent
    compiled_flops (incl. attention quadratic + remat recompute factor)."""
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        # fwd + 2x bwd (+ full fwd recompute under remat="layer";
        # "dots" saves matmul outputs -> ~0.3 pass of recompute)
        passes = {"layer": 4, "dots": 3.3, "none": 3}.get(cfg.remat, 4)
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        passes = 1
    else:  # decode: one token per sequence
        tokens = shape.global_batch * 1
        passes = 1

    n_active = cfg.active_param_count_estimate()
    model = 2 * n_active * tokens * (3 if shape.kind == "train" else 1)

    flops = 2 * n_active * tokens * passes
    # attention quadratic term (not in 6ND)
    if cfg.family in ("dense", "moe", "vlm"):
        kv_len = shape.seq_len
        att = _attention_flops(cfg, tokens, kv_len,
                               causal_half=causal_skip or
                               shape.kind == "decode")
        flops += att * (passes if shape.kind == "train" else 1)
    elif cfg.family == "audio":
        enc_tokens = shape.global_batch * shape.seq_len
        hd = cfg.resolved_head_dim
        enc_att = 2 * 2 * enc_tokens * shape.seq_len * cfg.n_heads * hd \
            * cfg.enc_layers
        flops += enc_att * (passes if shape.kind == "train" else 1)
    elif cfg.family == "hybrid":
        # mamba scan ~ linear; shared attention blocks quadratic
        g = max(1, cfg.num_layers // cfg.hybrid_attn_every)
        hd = cfg.resolved_head_dim
        kv_len = shape.seq_len
        att = 2 * 2 * tokens * kv_len * cfg.n_heads * hd * g
        if causal_skip or shape.kind == "decode":
            att /= 2
        flops += att * (passes if shape.kind == "train" else 1)
    # ssm (rwkv6): chunked linear attention is O(T·chunk·d) — inside 6ND
    # fudge already; add the state-expansion term
    if cfg.family == "ssm":
        h = cfg.d_model // cfg.ssm_head_dim
        p = cfg.ssm_head_dim
        flops += 2 * tokens * h * p * p * cfg.num_layers \
            * (passes if shape.kind == "train" else 1)
    return {"model_flops": float(model), "compiled_flops": float(flops)}


def step_hbm_bytes(cfg: ArchConfig, shape: ShapeConfig, chips: int) -> float:
    """Dominant HBM traffic per step across the whole job.

    Weights: streamed once per (micro)batch pass from each chip's HBM —
    weight bytes × passes × chips-that-hold-them (sharded: total = full
    weight bytes × passes × n_microbatches for train).
    KV cache: decode reads the full cache once per step.
    Activations: ~2 bytes × tokens × d × layers × passes (block I/O).
    """
    from repro.launch.specs import auto_microbatches
    pdt_bytes = 2 if cfg.param_dtype == "bfloat16" else 4
    weights = cfg.param_count_estimate() * pdt_bytes
    act_tokens = (shape.global_batch * shape.seq_len
                  if shape.kind != "decode" else shape.global_batch)
    layers = cfg.num_layers + (cfg.dec_layers if cfg.family == "audio"
                               else 0)
    acts = 2 * act_tokens * cfg.d_model * layers * 4  # r/w both ends
    if shape.kind == "train":
        n_mb = auto_microbatches(cfg, shape)
        passes = 3
        total = weights * passes * n_mb + acts * passes
        # optimizer state read+write once
        total += 2 * weights
    elif shape.kind == "prefill":
        total = weights + acts
    else:
        kvb = 1 if cfg.kv_cache_dtype.startswith("float8") else 2
        if cfg.family == "ssm":
            h = cfg.d_model // cfg.ssm_head_dim
            kv = (cfg.num_layers * shape.global_batch
                  * h * cfg.ssm_head_dim ** 2 * 4)
        elif cfg.family == "hybrid":
            g = max(1, cfg.num_layers // cfg.hybrid_attn_every)
            kv = (g * 2 * shape.global_batch * shape.seq_len
                  * cfg.n_kv_heads * cfg.resolved_head_dim * kvb)
            kv += (cfg.num_layers * shape.global_batch
                   * (2 * cfg.d_model // cfg.ssm_head_dim)
                   * cfg.ssm_head_dim * cfg.ssm_state * 4)
        else:
            layers_kv = (cfg.dec_layers if cfg.family == "audio"
                         else cfg.num_layers)
            kv_len = shape.seq_len
            kv = (layers_kv * 2 * shape.global_batch * kv_len
                  * cfg.n_kv_heads * cfg.resolved_head_dim * kvb)
        total = weights + kv + acts
    return float(total)


def collective_seconds(dryrun_row: dict, cfg: ArchConfig,
                       shape: ShapeConfig) -> float:
    """Collective bytes from HLO text × scan-trip rescale / ICI bandwidth.

    HLO counts in-while-body collectives once; the dominant in-body
    collectives run once per layer per microbatch, so we scale by
    layers (train: × microbatches handled via the already-unrolled µb scan
    being a while too — net factor L × n_mb for train, L otherwise).
    """
    from repro.launch.specs import auto_microbatches
    coll = dryrun_row.get("collectives", {})
    raw = coll.get("total_bytes", 0)
    layers = cfg.num_layers or (cfg.enc_layers + cfg.dec_layers)
    factor = layers
    if shape.kind == "train":
        factor *= auto_microbatches(cfg, shape)
    bytes_per_chip = raw * factor  # HLO shapes are already per-device
    return bytes_per_chip / (ICI_LINKS * ICI_BW)


@dataclasses.dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    compiled_flops: float
    useful_fraction: float
    mfu: float

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze(dryrun_row: dict, *, causal_skip: bool | None = None) -> RooflineRow:
    cfg = get_config(dryrun_row["arch"])
    shape = SHAPES[dryrun_row["shape"]]
    chips = dryrun_row["n_chips"]
    if causal_skip is None:
        causal_skip = cfg.skip_masked_chunks
    fl = step_flops(cfg, shape, causal_skip=causal_skip)
    compute_s = fl["compiled_flops"] / (chips * PEAK_FLOPS)
    memory_s = step_hbm_bytes(cfg, shape, chips) / (chips * HBM_BW)
    coll_s = collective_seconds(dryrun_row, cfg, shape)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": coll_s}
    bottleneck = max(terms, key=terms.get)
    step_time = max(terms.values())
    mfu = (fl["model_flops"] / (chips * PEAK_FLOPS)) / max(step_time, 1e-12)
    return RooflineRow(
        arch=dryrun_row["arch"], shape=dryrun_row["shape"],
        mesh=dryrun_row["mesh"], chips=chips,
        compute_s=compute_s, memory_s=memory_s, collective_s=coll_s,
        bottleneck=bottleneck,
        model_flops=fl["model_flops"],
        compiled_flops=fl["compiled_flops"],
        useful_fraction=fl["model_flops"] / max(
            fl["compiled_flops"] * (1 if shape.kind != "train" else 1), 1.0),
        mfu=mfu)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="results/dryrun.json")
    ap.add_argument("--out", default="results/roofline.json")
    ap.add_argument("--mesh", default="16x16",
                    help="roofline table mesh (single-pod per spec)")
    args = ap.parse_args(argv)
    rows = json.loads(Path(args.dryrun).read_text())
    out = []
    print(f"{'arch':22s} {'shape':12s} {'comp_s':>9s} {'mem_s':>9s} "
          f"{'coll_s':>9s} {'bound':>10s} {'MFU%':>6s} {'useful%':>8s}")
    for r in rows:
        if r.get("status") != "OK" or r["mesh"] != args.mesh:
            continue
        a = analyze(r)
        out.append(a.as_dict())
        print(f"{a.arch:22s} {a.shape:12s} {a.compute_s:9.4f} "
              f"{a.memory_s:9.4f} {a.collective_s:9.4f} "
              f"{a.bottleneck:>10s} {100*a.mfu:6.1f} "
              f"{100*a.useful_fraction:8.1f}")
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
