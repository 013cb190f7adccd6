"""The train step's stage scopes (``repro.core.stages``) reach the compiled
HLO's ``op_name``, each operation under the stage that raised it.

A tiny RegTop-k step over ``sparse_allgather`` (with the ``coo_q8`` codec,
whose encode computes: ``coo_fp32``'s is no operation) is compiled on one CPU
device and on two (the second with bucketed overlap, so every stage also
sits inside a ``spa_bucketNNN`` scope), in a subprocess so that this
process keeps seeing one device.
"""
import re
import textwrap

import pytest

from bench import stages as bench_stages
from bench import trace
from repro.core import stages
from tests.test_distributed import run_sub

SUB_CODE = textwrap.dedent(
    """
    import json
    import jax, jax.numpy as jnp
    from repro.compat import make_mesh
    from repro.core import distributed as D
    from repro.core.sparsify import SparsifierConfig
    from repro.data import TokenPipeline
    from repro.models import ModelConfig, get_family
    from repro.optim import OptConfig, make_optimizer

    W = len(jax.devices())
    mesh = make_mesh((W, 1), ("data", "model"))
    cfg = ModelConfig(name="tiny", family="dense", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                      vocab=128, remat=False)
    mod = get_family(cfg)
    dist = D.DistConfig(
        sparsifier=SparsifierConfig(kind="regtopk", sparsity=0.05, mu=1.0),
        optimizer=OptConfig(kind="adam", learning_rate=3e-3),
        codec="coo_q8", collective="sparse_allgather", dp_axes=("data",),
        overlap={OVERLAP!r})
    asm = D.assemble(mod, cfg, dist, mesh)
    params, _ = mod.init(jax.random.PRNGKey(0), cfg)
    opt_state = make_optimizer(dist.optimizer).init(params)
    sp_state, _ = D.init_sparsifier_state(asm.plan, W, mesh, ("data",),
                                          jnp.float32)
    batch = TokenPipeline(cfg, global_batch=4 * W, seq=16).batch_at(0)
    with mesh:
        hlo = jax.jit(asm.train_step).lower(
            params, opt_state, sp_state, batch).compile().as_text()
    print(json.dumps({"hlo": hlo}))
    """
)
_OP_NAME = re.compile(r"%([\w.\-]+) = .*?op_name=\"([^\"]*)\"")


@pytest.mark.parametrize("devices,overlap", [(1, "off"), (2, "buckets:2")])
def test_stage_scopes_reach_compiled_hlo(devices, overlap):
    hlo = run_sub(SUB_CODE.replace("{OVERLAP!r}", repr(overlap)),
                  devices=devices)["hlo"]
    layers = trace.hlo_layers(hlo)  # by opcode, name stack and frames
    seen = set()
    by_stage = {}
    for name, op_name in _OP_NAME.findall(hlo):
        layer, label = layers.get(name, ("other", ""))
        seen.update(s for s in re.split(r"[/;]", op_name) if s in stages.ALL)
        stage = bench_stages.stage_of(op_name)
        by_stage.setdefault(stage, []).append(label)
        if label == "top_k":
            assert stage == stages.SELECT, op_name
        if label == "all_gather":
            assert stage == stages.EXCHANGE, op_name
        if layer == "fwd_bwd":
            assert stage == stages.GRADS, op_name
        if layer == "optimizer":
            assert stage == stages.OPTIMIZER, op_name
        if overlap != "off" and stage.startswith("spa."):
            assert re.search(r"(^|[/;])spa_bucket\d{3}/", op_name), op_name
    assert seen == set(stages.ALL)
    assert set(bench_stages.STAGES) == set(stages.ALL)
    assert "top_k" in by_stage[stages.SELECT]
    assert "optimizer" in {layers[n][0] for n in layers}
    if devices > 1:
        assert "all_gather" in by_stage[stages.EXCHANGE]


def test_two_level_top_k_stays_in_select():
    """With leaves long enough for the two-level top-k (the threshold is
    lowered in the subprocess to fit the tiny model), its group maxima,
    sorts and candidate gather sit under ``spa.select``, in the round
    layer that ``round_ms`` reads."""
    code = SUB_CODE.replace("{OVERLAP!r}", repr("off")).replace(
        "import json\n",
        "import json\nfrom repro.core import compact\n"
        "compact.TWO_LEVEL_MIN_LEN = 64\n",
        1,
    )
    hlo = run_sub(code, devices=1)["hlo"]
    layers = trace.hlo_layers(hlo)
    select_labels = set()
    for name, op_name in _OP_NAME.findall(hlo):
        layer, label = layers.get(name, ("other", ""))
        stage = bench_stages.stage_of(op_name)
        if label in ("sort", "top_k") and op_name != "sort":
            # (a bare "sort" names a comparator's parameter)
            assert stage == stages.SELECT, op_name
        if stage == stages.SELECT:
            assert layer == "round", op_name
            select_labels.add(label)
    assert {"top_k", "sort", "gather", "reduce_max", "transpose"} <= select_labels
