"""§Perf summary — hillclimb before/after + multi-pod scaling, from artifacts."""
from __future__ import annotations

from benchmarks.common import row
from benchmarks.roofline import PEAKS, load

HILLCLIMBS = {
    ("deepseek-moe-16b", "train_4k"): [
        ("baseline", ""),
        ("H1.1 gather dispatch", "moe_gather"),
        ("H1.2 group 512", "moe_g512"),
        ("H1.3 +expert parallel", "moe_ep_g512"),
        ("H1.4 +dots remat", "moe_ep_g512_dots"),
    ],
    ("qwen2.5-3b", "train_4k"): [
        ("baseline", ""),
        ("H2.1 dots remat", "remat_dots"),
    ],
    ("phi3-medium-14b", "train_4k"): [
        ("baseline", ""),
        ("H3.1 pad heads 48", "pad_heads48"),
        ("H3.2 +dots remat", "pad_heads48_dots"),
    ],
    ("mixtral-8x7b", "prefill_32k"): [
        ("baseline", ""),
        ("H4.1 group 512", "moe_g512"),
    ],
    ("zamba2-7b", "train_4k"): [
        ("baseline", ""),
        ("H5.1 dots remat", "remat_dots"),
    ],
    ("whisper-tiny", "train_4k"): [
        ("baseline", ""),
        ("transfer: pad heads 16", "pad_heads16"),
    ],
    ("internvl2-1b", "train_4k"): [
        ("baseline", ""),
        ("transfer: pad heads 16", "pad_heads16"),
    ],
}


def run():
    rows = []
    recs = load()
    for (arch, shape), steps in HILLCLIMBS.items():
        for label, tag in steps:
            r = recs.get((arch, shape, "16x16", tag))
            if r is None:
                continue
            rows.append(
                row(
                    f"perf/{arch}/{label}",
                    0.0,
                    (
                        f"compute={r['flops'] / PEAKS.bf16_flops:.3e}s;"
                        f"collective={r['collective_bytes']['total'] / PEAKS.ici_bytes_per_s:.3e}s;"
                        f"peakGiB={r['mem']['peak_bytes'] / 2**30:.2f}"
                    ),
                )
            )
    # multi-pod scaling: collective growth when the pod axis joins dp
    for arch in ("qwen2.5-3b", "mixtral-8x7b", "mamba2-780m"):
        a = recs.get((arch, "train_4k", "16x16", ""))
        b = recs.get((arch, "train_4k", "2x16x16", ""))
        if a and b:
            rows.append(
                row(
                    f"perf/multipod/{arch}",
                    0.0,
                    (
                        f"coll_1pod={a['collective_bytes']['total']:.3e}B;"
                        f"coll_2pod={b['collective_bytes']['total']:.3e}B;"
                        "ratio={:.2f}".format(
                            b["collective_bytes"]["total"]
                            / a["collective_bytes"]["total"]
                        )
                    ),
                )
            )
    return rows
