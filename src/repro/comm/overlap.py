"""Bucketed overlap scheduling: hide the wire behind compute.

Every round used to be strictly compute-then-communicate: the full leaf
tree's collectives run after the whole backward finishes, so the wire time
RegTop-k exists to shrink still sits entirely on the critical path. This
module splits the leaf tree into size-balanced *buckets* and schedules each
bucket's collective as soon as its slice of the backward is done, pipelining
``hierarchical``'s slow inter-axis payload allgather behind the intra-axis
work of the next bucket.

Three pieces, all deterministic and static (trace-time planning — nothing
here touches tracers):

* :func:`bucketize` — greedy LPT bin-pack of per-leaf predicted wire
  seconds (from :func:`repro.comm.cost.stage_seconds`, the per-axis
  decomposition of ``cost.pattern_axes``) into :class:`BucketPlan`, with a
  balance factor and optional min/max bucket byte bounds. LPT guarantees
  ``max bucket seconds <= 4/3 * max(total/B, max leaf seconds)``; tighter
  ``balance_factor`` values are honored by reducing the bucket count until
  the bound holds (one bucket always does).
* :func:`overlap_timeline` — the two-stage pipeline recurrence producing
  per-bucket launch / intra-done / complete stamps and the overlapped round
  ``seconds``. The intra stage (innermost dp axis: ``hierarchical``'s dense
  psum, or a flat collective on a single-axis mesh) and the inter stage
  (outer axes: the payload allgather) are modeled as two serial resources,
  so bucket ``i+1``'s intra stage runs while bucket ``i``'s inter stage is
  still on the slow wire. At ``n_buckets=1`` the timeline reduces exactly
  to today's synchronous sum, and it never exceeds it.
* :func:`parse_overlap` — the CLI/``DistConfig.overlap`` spec grammar
  (``"off" | "buckets:B"``).

The *numerics* are untouched by construction: bucketing only reorders the
per-leaf sparsify+aggregate calls inside the traced round (each leaf's math
is independent), so ``overlap="off"`` and any bucket count are bit-for-bit
identical — asserted across codecs in ``tests/test_overlap.py`` and on a
real 8-device mesh in ``tests/test_distributed.py``. What changes is the
*schedule* the planner predicts (``CommPlan.buckets`` /
``CommPlan.timeline``, and ``comm_round_timeline`` for the live runtime)
and the profiler-visible structure of the round: each bucket runs under a
``jax.named_scope("spa_bucketNNN")`` annotation, around the round's stage
scopes (``repro.core.stages``).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Tuple

from repro.comm import cost as cost_lib
from repro.comm.cost import WORD_BYTES, AlphaBeta, LinkModel

# numeric slack for the balance-bound check: pure fp-summation noise must
# not force a pointless bucket-count reduction.
_BALANCE_TOL = 1e-9


@dataclasses.dataclass(frozen=True)
class OverlapConfig:
    """Bucketed-overlap planning knobs.

    ``n_buckets`` is the *requested* bucket count (clamped to the leaf
    count; :func:`bucketize` may merge below it to honor
    ``min_bucket_bytes`` or reduce it to honor ``balance_factor``).
    ``balance_factor`` bounds the load imbalance: every returned plan
    satisfies ``max bucket seconds <= balance_factor * max(total/B,
    max leaf seconds)`` — 4/3 is the classic LPT guarantee, so the
    default never forces a reduction. ``min_bucket_bytes`` merges
    too-small buckets (launch overhead amortization);
    ``max_bucket_bytes`` steers leaves away from over-full buckets
    (best effort — a single over-cap leaf still needs a home).
    """

    n_buckets: int = 1
    balance_factor: float = 4.0 / 3.0
    min_bucket_bytes: int = 0
    max_bucket_bytes: Optional[int] = None

    def __post_init__(self):
        if self.n_buckets < 1:
            raise ValueError(f"n_buckets must be >= 1, got {self.n_buckets}")
        if self.balance_factor < 1.0:
            raise ValueError(
                f"balance_factor must be >= 1.0, got {self.balance_factor}"
            )
        if self.min_bucket_bytes < 0:
            raise ValueError(
                f"min_bucket_bytes must be >= 0, got {self.min_bucket_bytes}"
            )
        if (
            self.max_bucket_bytes is not None
            and self.max_bucket_bytes < max(self.min_bucket_bytes, 1)
        ):
            raise ValueError(
                f"max_bucket_bytes={self.max_bucket_bytes} below "
                f"min_bucket_bytes={self.min_bucket_bytes} (or < 1)"
            )


def parse_overlap(spec: str) -> Optional[OverlapConfig]:
    """Parse a ``DistConfig.overlap`` / ``--overlap`` spec.

    Grammar: ``"off"`` (no bucketing — the historical synchronous round,
    bit-for-bit) or ``"buckets:B"`` with ``B >= 1``.

    >>> parse_overlap("off") is None
    True
    >>> parse_overlap("buckets:4").n_buckets
    4
    >>> parse_overlap("buckets:0")
    Traceback (most recent call last):
        ...
    ValueError: n_buckets must be >= 1, got 0
    >>> parse_overlap("stream")
    Traceback (most recent call last):
        ...
    ValueError: unknown overlap spec 'stream'; expected 'off' or 'buckets:B'
    """
    s = spec.strip()
    if s == "off":
        return None
    if s.startswith("buckets:"):
        body = s[len("buckets:"):]
        try:
            n = int(body)
        except ValueError:
            raise ValueError(
                f"overlap spec {spec!r}: bucket count {body!r} is not an int"
            ) from None
        return OverlapConfig(n_buckets=n)
    raise ValueError(
        f"unknown overlap spec {spec!r}; expected 'off' or 'buckets:B'"
    )


class LeafCost(NamedTuple):
    """One leaf's predicted wire cost, decomposed per dp mesh axis.

    ``axis_seconds`` follows the ``dp_sizes`` ordering (outermost/slowest
    first, innermost last) — the same per-axis attribution as
    :func:`repro.comm.cost.pattern_axes`. ``wire`` labels the (codec,
    collective) pair the seconds were priced under (informational; empty
    strings when the caller prices raw stage times)."""

    bytes_on_wire: int
    axis_seconds: Tuple[float, ...]
    wire: Tuple[str, str] = ("", "")

    @property
    def seconds(self) -> float:
        return float(sum(self.axis_seconds))


class Bucket(NamedTuple):
    """One scheduled bucket: the leaf indices it carries (ascending, into
    the flat plan order), its per-axis wire seconds (elementwise sums over
    its leaves), total predicted seconds/bytes, and the per-leaf (codec,
    collective) wire decisions riding in it."""

    leaves: Tuple[int, ...]
    seconds: float
    bytes_on_wire: int
    axis_seconds: Tuple[float, ...]
    wire: Tuple[Tuple[str, str], ...] = ()

    @property
    def intra_seconds(self) -> float:
        """Innermost-axis stage time (the fast dense psum / flat stage)."""
        return self.axis_seconds[-1] if self.axis_seconds else 0.0

    @property
    def inter_seconds(self) -> float:
        """Outer-axes stage time (the slow payload allgather)."""
        return float(sum(self.axis_seconds[:-1]))


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """An ordered bucket schedule over the flat leaf tree.

    Buckets are launched in order (bucket 0's backward slice finishes
    first); together they partition ``range(n_leaves)`` exactly — every
    leaf in exactly one bucket, asserted by the hypothesis properties in
    ``tests/test_overlap.py``."""

    buckets: Tuple[Bucket, ...]
    config: OverlapConfig

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def n_leaves(self) -> int:
        return sum(len(b.leaves) for b in self.buckets)

    def leaf_order(self) -> Tuple[int, ...]:
        """Leaf indices in launch order (bucket by bucket)."""
        return tuple(i for b in self.buckets for i in b.leaves)


class Timeline(NamedTuple):
    """Predicted per-bucket stamps of one overlapped round (seconds from
    round start): collective ``launch`` (backward slice done, intra stage
    free), ``intra_done`` (intra-axis stage finished), ``complete`` (inter
    stage drained). ``seconds`` is the overlapped round time
    (``complete[-1]``); ``sync_seconds`` the synchronous sum the same
    stages would take back-to-back — ``seconds <= sync_seconds`` always,
    with equality at one bucket."""

    launch: Tuple[float, ...]
    intra_done: Tuple[float, ...]
    complete: Tuple[float, ...]
    seconds: float
    sync_seconds: float


def leaf_cost(
    codec,
    collective: str,
    length: int,
    k: int,
    dp_sizes: Sequence[int],
    model: LinkModel = AlphaBeta(),
    word_bytes: int = WORD_BYTES,
    participants: Optional[float] = None,
) -> LeafCost:
    """Price one leaf for bucketing: predicted bytes plus per-axis stage
    seconds under ``model`` — the :func:`bucketize` input.

    On a slow-outer topology a ``hierarchical`` leaf splits into a large
    outer-axis (inter) stage and a small innermost (intra) stage — the
    two-resource shape :func:`overlap_timeline` pipelines:

    >>> from repro.comm.cost import AlphaBeta, LinkTopo
    >>> topo = LinkTopo((AlphaBeta(1e-5, 1e-9), AlphaBeta(1e-6, 1e-10)))
    >>> lc = leaf_cost("coo_fp32", "hierarchical", 10**6, 10**5, (2, 4), topo)
    >>> len(lc.axis_seconds)
    2
    >>> lc.wire
    ('coo_fp32', 'hierarchical')
    >>> abs(lc.seconds - sum(lc.axis_seconds)) < 1e-15
    True
    """
    est = cost_lib.predict(
        codec, collective, length, k, dp_sizes, model, word_bytes,
        participants,
    )
    ax = cost_lib.stage_seconds(
        codec, collective, length, k, dp_sizes, model, word_bytes,
        participants,
    )
    cname = codec if isinstance(codec, str) else codec.name
    return LeafCost(est.bytes_on_wire, ax, (cname, collective))


def _lpt_assign(costs, order, n_buckets, max_bytes):
    """Longest-processing-time greedy: place each leaf (descending
    seconds) into the least-loaded bucket, preferring buckets whose byte
    total stays under ``max_bytes`` (an empty bucket always accepts)."""
    loads = [0.0] * n_buckets
    nbytes = [0] * n_buckets
    bins: list = [[] for _ in range(n_buckets)]
    for i in order:
        cand = sorted(range(n_buckets), key=lambda j: (loads[j], j))
        pick = cand[0]
        if max_bytes is not None:
            for j in cand:
                if not bins[j] or nbytes[j] + costs[i].bytes_on_wire <= max_bytes:
                    pick = j
                    break
        bins[pick].append(i)
        loads[pick] += costs[i].seconds
        nbytes[pick] += costs[i].bytes_on_wire
    return [b for b in bins if b]


def _merge_small(bins, costs, min_bytes):
    """Fold buckets under ``min_bytes`` into the least-loaded survivor."""
    if min_bytes <= 0:
        return bins
    bins = [list(b) for b in bins]
    while len(bins) > 1:
        sizes = [sum(costs[i].bytes_on_wire for i in b) for b in bins]
        small = min(range(len(bins)), key=lambda j: (sizes[j], j))
        if sizes[small] >= min_bytes:
            break
        loads = [sum(costs[i].seconds for i in b) for b in bins]
        other = min(
            (j for j in range(len(bins)) if j != small),
            key=lambda j: (loads[j], j),
        )
        bins[other].extend(bins[small])
        del bins[small]
    return bins


def bucketize(
    costs: Sequence[LeafCost], config: OverlapConfig = OverlapConfig()
) -> BucketPlan:
    """Greedy size-balanced bin-pack of the leaf tree into a bucket
    schedule.

    Deterministic LPT: leaves sorted by descending predicted seconds (ties
    by index) go to the least-loaded bucket, honoring
    ``config.max_bucket_bytes`` when possible; buckets under
    ``config.min_bucket_bytes`` are merged away; if the result violates
    ``config.balance_factor`` the bucket count is reduced until it holds
    (a single bucket trivially does). Returned buckets are ordered by
    their smallest leaf index — the launch order of the backward slices —
    and partition ``range(len(costs))`` exactly.

    >>> costs = [LeafCost(400, (3e-3,)), LeafCost(400, (3e-3,)),
    ...          LeafCost(200, (1e-3,)), LeafCost(200, (1e-3,))]
    >>> bp = bucketize(costs, OverlapConfig(n_buckets=2))
    >>> [b.leaves for b in bp.buckets]
    [(0, 2), (1, 3)]
    >>> sorted(bp.leaf_order())
    [0, 1, 2, 3]
    >>> bucketize(costs, OverlapConfig(n_buckets=2,
    ...                                min_bucket_bytes=10**6)).n_buckets
    1
    """
    costs = list(costs)
    if not costs:
        raise ValueError("bucketize needs at least one leaf cost")
    n_axes = len(costs[0].axis_seconds)
    if any(len(c.axis_seconds) != n_axes for c in costs):
        raise ValueError(
            "every LeafCost must decompose over the same dp axes"
        )
    order = sorted(range(len(costs)), key=lambda i: (-costs[i].seconds, i))
    total = sum(c.seconds for c in costs)
    max_leaf = max(c.seconds for c in costs)
    assign = [order]
    for nb in range(min(config.n_buckets, len(costs)), 0, -1):
        assign = _merge_small(
            _lpt_assign(costs, order, nb, config.max_bucket_bytes),
            costs,
            config.min_bucket_bytes,
        )
        loads = [sum(costs[i].seconds for i in b) for b in assign]
        ideal = max(total / len(assign), max_leaf)
        if (
            len(assign) == 1
            or max(loads) <= config.balance_factor * ideal + _BALANCE_TOL
        ):
            break
    buckets = []
    for b in sorted(assign, key=min):
        idxs = tuple(sorted(b))
        ax = tuple(
            sum(costs[i].axis_seconds[a] for i in idxs)
            for a in range(n_axes)
        )
        buckets.append(
            Bucket(
                leaves=idxs,
                seconds=float(sum(ax)),
                bytes_on_wire=sum(costs[i].bytes_on_wire for i in idxs),
                axis_seconds=ax,
                wire=tuple(costs[i].wire for i in idxs),
            )
        )
    return BucketPlan(buckets=tuple(buckets), config=config)


def overlap_timeline(
    plan: BucketPlan,
    compute_seconds: Optional[Sequence[float]] = None,
) -> Timeline:
    """Predicted timeline of one overlapped round.

    Two serial resources, pipelined across buckets: the *intra* stage
    (innermost dp axis — ``hierarchical``'s dense psum, or the whole
    collective on a single-axis mesh) and the *inter* stage (outer axes —
    the payload allgather on the slow wire). Bucket ``i`` launches once
    its backward slice is done (``compute_seconds[i]``, cumulative) *and*
    the intra stage is free; its inter stage then drains behind the next
    bucket's intra work:

        ``launch[i]     = max(compute_done[i], intra_done[i-1])``
        ``intra_done[i] = launch[i] + intra[i]``
        ``complete[i]   = max(intra_done[i], complete[i-1]) + inter[i]``

    ``seconds = complete[-1]``; ``sync_seconds`` is the synchronous sum of
    every stage back-to-back. By induction ``seconds <= sync_seconds``,
    with exact equality at one bucket (no ``compute_seconds``):

    >>> two = bucketize([LeafCost(100, (2e-3, 1e-3)),
    ...                  LeafCost(100, (2e-3, 1e-3))],
    ...                 OverlapConfig(n_buckets=2))
    >>> tl = overlap_timeline(two)
    >>> tl.seconds < tl.sync_seconds
    True
    >>> one = overlap_timeline(bucketize([LeafCost(100, (2e-3, 1e-3))]))
    >>> one.seconds == one.sync_seconds
    True
    """
    comp = (
        [0.0] * plan.n_buckets
        if compute_seconds is None
        else [float(c) for c in compute_seconds]
    )
    if len(comp) != plan.n_buckets:
        raise ValueError(
            f"compute_seconds has {len(comp)} entries for "
            f"{plan.n_buckets} buckets"
        )
    if any(c < 0 for c in comp):
        raise ValueError("compute_seconds must be non-negative")
    launch, intra_done, complete = [], [], []
    comp_done = 0.0
    intra_free = 0.0
    inter_free = 0.0
    for b, c in zip(plan.buckets, comp, strict=True):
        comp_done += c
        t_launch = max(comp_done, intra_free)
        t_intra = t_launch + b.intra_seconds
        intra_free = t_intra
        t_complete = max(t_intra, inter_free) + b.inter_seconds
        inter_free = t_complete
        launch.append(t_launch)
        intra_done.append(t_intra)
        complete.append(t_complete)
    sync = sum(comp) + sum(b.seconds for b in plan.buckets)
    return Timeline(
        launch=tuple(launch),
        intra_done=tuple(intra_done),
        complete=tuple(complete),
        seconds=complete[-1],
        sync_seconds=sync,
    )
