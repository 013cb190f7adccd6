"""Roofline terms per (arch x shape) from the dry-run artifacts.

Reads benchmarks/artifacts/dryrun.jsonl (written by repro.launch.dryrun):
  compute term    = flops / peak_flops            [per chip, s]
  memory term     = hbm_bytes / hbm_bw            [per chip, s]
  collective term = collective_bytes / ici_bw     [per chip, s]
plus MODEL_FLOPS = 6 N_active D (train) / 2 N_active (decode per token)
and the usefulness ratio MODEL_FLOPS / HLO_FLOPS.
"""
from __future__ import annotations

import json
import os
from typing import Dict

from benchmarks.common import row
from repro import configs as cfglib
from repro.launch.mesh import chip_peaks

# the dry run lowers for the production mesh, a TPU v5e slice
PEAKS = chip_peaks("TPU v5 lite")

ART = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun.jsonl")


def count_params(cfg) -> Dict[str, float]:
    """Analytic parameter counts (total and per-token-active)."""
    d, V = cfg.d_model, cfg.padded_vocab
    emb = V * d
    attn = (
        d * cfg.n_heads * cfg.hd * 2
        + d * cfg.n_kv_heads * cfg.hd * 2
    ) if cfg.n_heads else 0
    if cfg.is_moe:
        expert = 3 * d * cfg.d_ff
        shared = 3 * d * (cfg.moe_shared_d_ff or 0)
        mlp_total = cfg.n_experts * expert + shared + d * cfg.n_experts
        mlp_active = cfg.moe_top_k * expert + shared + d * cfg.n_experts
    elif cfg.d_ff:
        n_mats = 3 if cfg.act == "swiglu" else 2
        mlp_total = mlp_active = n_mats * d * cfg.d_ff
    else:
        mlp_total = mlp_active = 0
    ssm = 0
    if cfg.ssm_state:
        di = cfg.d_inner
        ssm = d * (2 * di + 2 * cfg.ssm_state + di // cfg.ssm_headdim) + di * d
    if cfg.family == "hybrid":
        per = cfg.attn_every
        n_groups = cfg.n_layers // per
        n_mamba = cfg.n_layers - n_groups
        total = emb + n_mamba * ssm + (attn + mlp_total)  # shared attn once
        active = emb + n_mamba * ssm + n_groups * (attn + mlp_active)
    elif cfg.family == "ssm":
        total = active = emb + cfg.n_layers * ssm
    elif cfg.family == "encdec":
        total = active = emb + cfg.n_enc_layers * (attn + mlp_total) + (
            cfg.n_layers * (2 * attn + mlp_total)
        )
    else:
        total = emb + cfg.n_layers * (attn + mlp_total)
        active = emb + cfg.n_layers * (attn + mlp_active)
    return {"total": float(total), "active": float(active)}


def model_flops(arch: str, shape: str) -> float:
    """Whole-system MODEL_FLOPS (all chips) for the step."""
    cfg = cfglib.get_config(arch)
    seq, batch, kind = cfglib.INPUT_SHAPES[shape]
    p = count_params(cfg)
    if kind == "train":
        return 6.0 * p["active"] * batch * seq
    if kind == "prefill":
        return 2.0 * p["active"] * batch * seq
    return 2.0 * p["active"] * batch  # decode: one token per sequence


def load(path: str = ART):
    recs = {}
    if not os.path.exists(path):
        return recs
    with open(path) as f:
        for line in f:
            try:
                r = json.loads(line)
            except Exception:
                continue
            recs[(r["arch"], r["shape"], r["mesh"], r.get("tag", ""))] = r
    return recs


def analytic_bytes(arch: str, shape: str, mesh_model: int = 16,
                   dp: int = 16) -> float:
    """Per-chip HBM traffic estimate at TPU fusion granularity.

    Weights stream: params(+opt moments+eps) r/w; activation stream:
    ~12 materialized tensors x d_model per token per layer (fwd+bwd+remat),
    halved for the model-sharded fraction. The HLO-derived ``hbm_bytes``
    is an upper bound at CPU fusion granularity; this is the napkin lower
    estimate — both are reported, the dominant term uses this one.
    """
    cfg = cfglib.get_config(arch)
    seq, batch, kind = cfglib.INPUT_SHAPES[shape]
    p = count_params(cfg)
    dt = 2.0  # bf16
    params_chip = p["total"] * dt / mesh_model
    L = cfg.n_layers + cfg.n_enc_layers
    if kind == "train":
        tokens_chip = batch * seq / dp
        weight_stream = params_chip * 6  # fwd+bwd reads, grad/opt/eps r/w
        act_stream = tokens_chip * cfg.d_model * L * 12 * dt * 0.5
        return weight_stream + act_stream
    if kind == "prefill":
        tokens_chip = batch * seq / dp
        return params_chip + tokens_chip * cfg.d_model * L * 8 * dt * 0.5
    # decode: weights + kv-cache read per token + state r/w
    dp_eff = dp if batch % dp == 0 else 1
    if cfg.ssm_state or cfg.family == "hybrid":
        cache = 0.0
        if cfg.family == "hybrid":
            n_groups = cfg.n_layers // cfg.attn_every
            cache = (
                n_groups * batch * seq * cfg.n_kv_heads * cfg.hd
                * dt / (mesh_model * dp_eff)
            )
        state = batch * (cfg.d_inner * cfg.ssm_state) * L * dt / mesh_model
        return params_chip + cache + 2 * state
    slots = min(seq, cfg.sliding_window or seq)
    cache = (
        L * batch * slots * cfg.n_kv_heads * cfg.hd * dt
        / (mesh_model * dp_eff)
    )
    return params_chip + cache


def terms(rec, n_chips: int) -> Dict[str, float]:
    comp = rec["flops"] / PEAKS.bf16_flops
    mem_hlo = rec["hbm_bytes"] / PEAKS.hbm_bytes_per_s
    memt = analytic_bytes(rec["arch"], rec["shape"]) / PEAKS.hbm_bytes_per_s
    coll = rec["collective_bytes"]["total"] / PEAKS.ici_bytes_per_s
    dom = max(("compute", comp), ("memory", memt), ("collective", coll),
              key=lambda kv: kv[1])
    mf = model_flops(rec["arch"], rec["shape"]) / n_chips
    return {
        "compute_s": comp,
        "memory_s": memt,
        "memory_hlo_ub_s": mem_hlo,
        "collective_s": coll,
        "dominant": dom[0],
        "model_flops_per_chip": mf,
        "useful_ratio": mf / rec["flops"] if rec["flops"] else 0.0,
    }


def run():
    rows = []
    recs = load()
    for (arch, shape, mesh, tag), rec in sorted(recs.items()):
        if mesh != "16x16" or tag:
            continue
        n_chips = 256
        t = terms(rec, n_chips)
        rows.append(
            row(
                f"roofline/{arch}/{shape}",
                0.0,
                (
                    f"compute={t['compute_s']:.3e}s;memory={t['memory_s']:.3e}s;"
                    f"memory_hlo_ub={t['memory_hlo_ub_s']:.3e}s;"
                    f"collective={t['collective_s']:.3e}s;dominant={t['dominant']};"
                    f"useful_ratio={t['useful_ratio']:.3f};"
                    f"peakGiB={rec['mem']['peak_bytes']/2**30:.2f}"
                ),
            )
        )
    return rows
