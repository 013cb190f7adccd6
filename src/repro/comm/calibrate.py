"""Micro-calibration: time real collectives, fit alpha–beta link models.

The planner (:mod:`repro.comm.autotune`) is only as good as its alpha/beta.
This module probes the *actual* backend with raw collectives — a psum of a
dense [L] vector and an all_gather of a B-byte buffer over the dp axes —
at a geometric ladder of sizes, then least-squares fits

    seconds = n_messages * alpha + bytes_on_wire * beta

over the measured (n_messages, bytes_on_wire, seconds) samples, where the
message/byte counts come from the same ring patterns the cost model scores
(:func:`repro.comm.cost._pattern`). Two entry points:

* ``calibrate()`` — one :class:`AlphaBeta` for the whole dp group: builds a
  dp mesh over the available devices and returns the fitted model plus the
  raw samples; on a single device there is no wire to probe and it falls
  back to the default model (``calibrated=False``).
* ``calibrate_topo()`` — one :class:`AlphaBeta` *per dp mesh axis*: probes
  collectives along each axis separately (the other axes stay idle), so an
  intra-node NVLink/ICI axis and an inter-node NIC axis each get their own
  fit. The result's :class:`~repro.comm.cost.LinkTopo` drops straight into
  ``DistConfig.link_topo`` / the planner's ``model=`` argument.

Caveats (by design — this is a micro-harness, not a benchmark suite):
timings include shard_map dispatch overhead, so alpha absorbs the launch
cost, and per-axis probes time each link class under an otherwise-idle
mesh (no congestion between classes).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.comm.cost import AlphaBeta, LinkTopo, _pattern
from repro.compat import make_mesh

DEFAULT_LENGTHS = (1 << 12, 1 << 14, 1 << 16, 1 << 18)


def _resolve_mesh(mesh, dp_axes):
    """Default mesh/axes discovery shared by the calibrate entry points:
    with no mesh, probe all local devices on one ("data",) axis. A caller
    supplying ``dp_axes`` without the mesh that defines them is ambiguous
    — refuse rather than silently probing a different topology."""
    if mesh is None:
        if dp_axes is not None:
            raise ValueError(
                "dp_axes without a mesh is ambiguous: pass the mesh whose "
                f"axes {tuple(dp_axes)} should be probed"
            )
        n = len(jax.devices())
        if n >= 2:
            mesh = make_mesh((n,), ("data",))
        return mesh, ("data",)
    return mesh, tuple(dp_axes or ("data",))


@dataclasses.dataclass(frozen=True)
class Sample:
    """One timed collective: the fit's (features, target) row."""

    collective: str
    length: int
    n_messages: int
    bytes_on_wire: int
    seconds: float


@dataclasses.dataclass(frozen=True)
class Calibration:
    model: AlphaBeta
    samples: Tuple[Sample, ...]
    calibrated: bool
    residual: float  # RMS of the fit, seconds


@dataclasses.dataclass(frozen=True)
class TopoCalibration:
    """Per-axis calibrations plus the :class:`LinkTopo` they assemble into.

    ``axes`` names the dp mesh axes (outermost first); ``per_axis[i]`` is
    that axis's own :class:`Calibration` (``calibrated=False`` for size-1
    axes, which have no wire to probe). ``calibrated`` is True when at
    least one axis was actually timed.
    """

    topo: LinkTopo
    per_axis: Tuple[Calibration, ...]
    axes: Tuple[str, ...]
    calibrated: bool


def fit_alpha_beta(
    samples: Sequence[Sample],
    floor_alpha: float = 1e-9,
    floor_beta: float = 1e-14,
) -> AlphaBeta:
    """Non-negative least squares (clamped) over the sample rows.

    >>> rows = [Sample("probe", i, m, b, m * 2e-5 + b * 3e-10)
    ...         for i, (m, b) in enumerate([(7, 1000), (14, 100000),
    ...                                     (3, 5000000)])]
    >>> fit = fit_alpha_beta(rows)
    >>> round(fit.alpha, 9), round(fit.beta, 14)
    (2e-05, 3e-10)
    """
    if not samples:
        raise ValueError("cannot fit AlphaBeta from zero samples")
    A = np.array(
        [[s.n_messages, s.bytes_on_wire] for s in samples], np.float64
    )
    t = np.array([s.seconds for s in samples], np.float64)
    x, *_ = np.linalg.lstsq(A, t, rcond=None)
    alpha, beta = float(x[0]), float(x[1])
    # a negative coefficient means the other term explains everything at
    # these sizes; clamp and refit the remaining term alone.
    if alpha < floor_alpha and beta < floor_beta:
        return AlphaBeta(alpha=floor_alpha, beta=floor_beta)
    if alpha < floor_alpha:
        beta = max(float(t @ A[:, 1] / (A[:, 1] @ A[:, 1])), floor_beta)
        return AlphaBeta(alpha=floor_alpha, beta=beta)
    if beta < floor_beta:
        alpha = max(float(t @ A[:, 0] / (A[:, 0] @ A[:, 0])), floor_alpha)
        return AlphaBeta(alpha=alpha, beta=floor_beta)
    return AlphaBeta(alpha=alpha, beta=beta)


def _time_call(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def time_collective(
    mesh,
    dp_axes: Sequence[str],
    length: int,
    collective: str = "dense_allreduce",
    word_bytes: int = 4,
    iters: int = 5,
) -> Sample:
    """Time one real collective over the mesh's dp axes.

    ``dense_allreduce`` psums a dense float32 [L]; ``sparse_allgather``
    all_gathers a ``length``-word buffer (the payload stand-in — the wire
    doesn't care what the words mean).

    >>> s = time_collective(mesh, ("data",), 4096)  # doctest: +SKIP
    >>> s.n_messages  # 2·(N-1) ring steps          # doctest: +SKIP
    14
    """
    dp = tuple(dp_axes)
    dp_spec = dp if len(dp) > 1 else dp[0]
    W = int(np.prod([mesh.shape[a] for a in dp]))

    if collective == "dense_allreduce":

        def body(x):  # x local [1, L]
            return jax.lax.psum(x, dp)

        out_spec = P(None, None)
        payload_bytes = 0.0  # dense term carries the bytes
    elif collective == "sparse_allgather":

        def body(x):  # x local [1, L] -> gathered [W, L], reduced locally
            g = x
            for ax in dp:
                g = jax.lax.all_gather(g, ax)
            return g.reshape(-1, x.shape[-1]).sum(axis=0, keepdims=True)

        out_spec = P(None, None)
        payload_bytes = length * word_bytes
    else:
        raise ValueError(
            f"calibration probe for {collective!r} not implemented; "
            "use 'dense_allreduce' or 'sparse_allgather'"
        )

    f = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=P(dp_spec, None),
            out_specs=out_spec,
            check_vma=False,
        )
    )
    x = jnp.ones((W, length), jnp.float32)
    secs = _time_call(f, x, iters=iters)
    dp_sizes = [mesh.shape[a] for a in dp]
    by, msgs = _pattern(collective, length, payload_bytes, dp_sizes, word_bytes)
    return Sample(
        collective=collective,
        length=length,
        n_messages=msgs,
        bytes_on_wire=int(np.ceil(by)),
        seconds=secs,
    )


def calibrate(
    mesh=None,
    dp_axes: Optional[Sequence[str]] = None,
    lengths: Sequence[int] = DEFAULT_LENGTHS,
    collectives: Sequence[str] = ("dense_allreduce", "sparse_allgather"),
    iters: int = 5,
) -> Calibration:
    """Probe the backend and fit AlphaBeta. A dp group of fewer than two
    workers (single device, or a caller mesh with dp size 1) has no wire to
    probe: every sample row would be (0 messages, 0 bytes) and the fit
    degenerates to the clamp floors — fall back to the default model.

    >>> from repro.compat import make_mesh
    >>> res = calibrate(mesh=make_mesh((1,), ("data",)), dp_axes=("data",))
    >>> res.calibrated, res.model == AlphaBeta()
    (False, True)
    """
    mesh, dp_axes = _resolve_mesh(mesh, dp_axes)
    n_dp = (
        int(np.prod([mesh.shape[a] for a in dp_axes])) if mesh is not None
        else 1
    )
    if n_dp < 2:
        return Calibration(
            model=AlphaBeta(), samples=(), calibrated=False, residual=0.0
        )
    samples: List[Sample] = []
    for coll in collectives:
        for L in lengths:
            samples.append(
                time_collective(mesh, dp_axes, L, coll, iters=iters)
            )
    model = fit_alpha_beta(samples)
    pred = np.array(
        [s.n_messages * model.alpha + s.bytes_on_wire * model.beta
         for s in samples]
    )
    meas = np.array([s.seconds for s in samples])
    rms = float(np.sqrt(np.mean((pred - meas) ** 2)))
    return Calibration(
        model=model, samples=tuple(samples), calibrated=True, residual=rms
    )


def calibrate_topo(
    mesh=None,
    dp_axes: Optional[Sequence[str]] = None,
    lengths: Sequence[int] = DEFAULT_LENGTHS,
    collectives: Sequence[str] = ("dense_allreduce", "sparse_allgather"),
    iters: int = 5,
) -> TopoCalibration:
    """Fit one :class:`AlphaBeta` *per dp mesh axis* by timing collectives
    along each axis separately (the other axes sit idle), assembling a
    :class:`~repro.comm.cost.LinkTopo` ordered like ``dp_axes`` (outermost
    first). Size-1 axes have no wire to probe and keep the default model
    with ``calibrated=False`` in their per-axis entry.

    With no mesh given, mirrors :func:`calibrate`'s device discovery: all
    local devices on one ``("data",)`` axis — per-axis calibration then
    degenerates to the uniform fit. Pass the real training mesh (e.g.
    ``("pod", "data")``) to resolve distinct link classes.

    >>> from repro.compat import make_mesh
    >>> res = calibrate_topo(mesh=make_mesh((1, 1), ("pod", "data")),
    ...                      dp_axes=("pod", "data"))
    >>> res.calibrated, res.topo.n_axes
    (False, 2)
    """
    mesh, dp_axes = _resolve_mesh(mesh, dp_axes)
    per_axis: List[Calibration] = []
    for ax in dp_axes:
        size = mesh.shape[ax] if mesh is not None else 1
        if size < 2:
            per_axis.append(
                Calibration(
                    model=AlphaBeta(), samples=(), calibrated=False,
                    residual=0.0,
                )
            )
            continue
        per_axis.append(
            calibrate(
                mesh=mesh,
                dp_axes=(ax,),
                lengths=lengths,
                collectives=collectives,
                iters=iters,
            )
        )
    return TopoCalibration(
        topo=LinkTopo(tuple(c.model for c in per_axis)),
        per_axis=tuple(per_axis),
        axes=dp_axes,
        calibrated=any(c.calibrated for c in per_axis),
    )
