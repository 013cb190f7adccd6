"""Compile every Pallas kernel for a described TPU v5e chip.

Interpret mode cannot see the TPU compiler's layout rules (block shapes,
scalar stores, memory spaces). Here each kernel is compiled with
``interpret=False`` at two whisper-tiny leaf sizes — the padded
52224 x 384 embedding and the stacked 4 x 384 x 1536 FFN weight — for a
v5e chip that is described, not attached, and the program must hold a
Mosaic kernel (``tpu_custom_call``). Nothing runs, so no chip is needed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the tests of this file run in the
process that is given the file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm.fastpath import candidate_budget
from repro.core.selectors import sparsity_to_k
from repro.kernels import (
    block_topk,
    fused_encode,
    ops,
    regtopk_score,
    threshold_topk,
)

LEAVES = {"embed": 52224 * 384, "ffn": 4 * 384 * 1536}
SPARSITY = 0.01


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means no chip desc
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _kernel(name, length):
    """The kernel ``name`` as a function of one [rows, 1024] f32 array
    holding a ``length``-element leaf."""
    k = sparsity_to_k(length, SPARSITY)
    if name == "regtopk_score":
        return lambda x: regtopk_score.regtopk_score(
            x, x, x, x, omega=0.25, mu=1.0
        )
    if name == "fused_candidates":
        m = candidate_budget(length, k)
        return lambda x: fused_encode.fused_candidates(
            x, x, x, x, omega=0.25, mu=1.0, m=m
        )
    if name == "block_topk_candidates":
        return lambda x: block_topk.block_topk_candidates(x, m=8)
    if name == "count_above":
        return lambda x: threshold_topk.count_above(x, jnp.float32(0.5))
    if name == "global_max":
        return threshold_topk.global_max
    raise AssertionError(name)


@pytest.mark.parametrize("leaf", sorted(LEAVES))
@pytest.mark.parametrize(
    "name",
    [
        "regtopk_score",
        "fused_candidates",
        "block_topk_candidates",
        "count_above",
        "global_max",
    ],
)
def test_kernel_compiles_for_v5e(one_chip, name, leaf):
    length = LEAVES[leaf]
    rows = -(-length // ops.TILE) * ops.SUBLANES
    x = jax.ShapeDtypeStruct((rows, ops.LANES), jnp.float32, sharding=one_chip)
    hlo = jax.jit(_kernel(name, length)).lower(x).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_exact_selection_sorts_no_whole_leaf(one_chip):
    """RegTop-k's exact selection on a leaf long enough for the two-level
    top-k compiles for the v5e with no sort over more than a quarter of
    the leaf (one ``lax.top_k`` compiles to a stable sort of the whole
    leaf)."""
    import re

    from repro.core import compact
    from repro.core.sparsify import SparsifierConfig

    length = max(compact.TWO_LEVEL_MIN_LEN, 1 << 22)
    k = sparsity_to_k(length, SPARSITY)
    cfg = SparsifierConfig(kind="regtopk", sparsity=SPARSITY, mu=1.0)
    st = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(lambda: compact.compact_init(length, k)),
    )
    g = jax.ShapeDtypeStruct((length,), jnp.float32, sharding=one_chip)
    hlo = (
        jax.jit(lambda st, g: compact.compact_select(cfg, st, g, k))
        .lower(st, g)
        .compile()
        .as_text()
    )
    sorts = re.findall(r"= (.*?) sort\(", hlo)
    assert sorts
    longest = max(int(n) for s in sorts for n in re.findall(r"\[(\d+)\]", s))
    assert longest <= length // 4, longest
