"""Wire codecs: interchangeable payload encodings with exact bit accounting.

A codec turns the sparsifier's fixed-k payload ``(vals [k], idx [k])`` over a
length-``L`` flat gradient shard into a pytree of statically-shaped arrays
(the *wire payload*) and back. Static shapes are non-negotiable: the payload
is what ``all_gather`` moves across the data-parallel mesh axes, so every
leaf's shape/dtype must be a pure function of ``(L, k)`` — never of the data.

Implemented codecs (paper Sec. 2.2 moves ``2·N·k`` words; these shrink the
constant in front):

* ``coo_fp32``      — fp32 values + int32 indices. The baseline wire format
  (exactly the pre-``repro.comm`` behavior): 64 bits/coordinate.
* ``coo_idx_delta`` — indices sorted ascending and delta-encoded. Sorted
  deltas are bounded by ``L - 1``, so the delta dtype is chosen *statically*
  from ``L`` (int8 for L < 2^7, int16 for L < 2^15, else int32 — no win).
  Lossless; 32 + 8/16 bits per coordinate on small/medium shards.
* ``bitmap_dense``  — a 1-bit presence bitmap (packed uint8) + the k values
  in index-ascending order. ``L + 32·k`` bits: beats COO's ``32·k`` index
  cost whenever S = k/L > 1/32.
* ``coo_q8``        — int8-quantized values (symmetric per-payload scale) +
  int32 indices. Lossy: the quantization residual must be folded back into
  the sparsifier's error accumulator ``eps`` (error feedback covers the
  codec); callers do that via :func:`decoded_dense` — see
  ``distributed._spa_leaf`` / ``simulator.step_fn``.

Round-trip contract: ``decode(encode(vals, idx)) == (vals', idx')`` such that
``scatter_add(vals', idx') == scatter_add(vals, idx)`` exactly for lossless
codecs (decode may reorder coordinates and merge duplicate padding slots).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Payload = Dict[str, jax.Array]


def _scatter_dense(vals: jax.Array, idx: jax.Array, length: int) -> jax.Array:
    return jnp.zeros((length,), vals.dtype).at[idx].add(vals)


class Codec:
    """Base codec. Subclasses set ``name``/``lossless`` and implement
    ``encode``/``decode``/``wire_bits``; codecs whose encoding is a pure
    function of the k selected ``(vals, idx)`` registers additionally
    implement :meth:`encode_fused` (and set ``supports_fused``) so the
    fused select→encode fastpath can emit their payload without any dense
    intermediate — see :mod:`repro.comm.fastpath`."""

    name: str = "base"
    lossless: bool = True
    supports_fused: bool = False

    def encode(self, vals: jax.Array, idx: jax.Array, length: int) -> Payload:
        raise NotImplementedError

    def encode_fused(
        self, vals: jax.Array, idx: jax.Array, length: int
    ) -> Payload:
        """Optional hook: encode straight from the fused pipeline's
        ``(vals [k], idx [k])`` output. Must produce a payload
        bit-identical to ``encode`` on the same inputs; the difference is
        the *contract* — no dense [L] intermediate may be touched, so the
        epilogue fuses behind the selection kernel. Codecs whose wire
        format is inherently dense (``bitmap_dense``) leave this
        unimplemented."""
        raise NotImplementedError(
            f"codec {self.name!r} has no fused encode epilogue"
        )

    def decode(
        self, payload: Payload, length: int
    ) -> Tuple[jax.Array, jax.Array]:
        """Returns ``(vals [k], idx [k])``; padding slots decode to (0, 0)."""
        raise NotImplementedError

    def wire_bits(self, length: int, k: int) -> int:
        """Exact payload size in bits — the codec's bit accounting."""
        raise NotImplementedError

    # -- shared helpers ----------------------------------------------------
    def decoded_dense(self, payload: Payload, length: int) -> jax.Array:
        """Dense [L] view of what this payload actually carries. For lossy
        codecs this is what the receiver reconstructs — the sender folds
        ``intended - decoded_dense`` back into ``eps`` (error feedback)."""
        vals, idx = self.decode(payload, length)
        return _scatter_dense(vals, idx, length)


class CooFp32(Codec):
    """fp32 values + int32 indices — the uncompressed-index baseline."""

    name = "coo_fp32"
    lossless = True
    supports_fused = True

    def encode(self, vals, idx, length):
        return {"vals": vals.astype(jnp.float32), "idx": idx.astype(jnp.int32)}

    def encode_fused(self, vals, idx, length):
        """Pure register passthrough — the COO payload *is* the fused
        pipeline's output."""
        return self.encode(vals, idx, length)

    def decode(self, payload, length):
        return payload["vals"], payload["idx"]

    def wire_bits(self, length, k):
        return 32 * k + 32 * k


def delta_index_dtype(length: int):
    """Static dtype for sorted-index deltas: every delta (and the leading
    absolute index) is < ``length``, so the choice depends only on L.

    >>> delta_index_dtype(100) is jnp.int8
    True
    >>> delta_index_dtype(1 << 14) is jnp.int16
    True
    >>> delta_index_dtype(1 << 20) is jnp.int32
    True
    """
    if length < 2**7:
        return jnp.int8
    if length < 2**15:
        return jnp.int16
    return jnp.int32


class CooIdxDelta(Codec):
    """Sorted indices, delta-encoded in the narrowest statically-safe int."""

    name = "coo_idx_delta"
    lossless = True
    supports_fused = True

    def encode_fused(self, vals, idx, length):
        """k-sized sort + diff over the selected registers — O(k log k)
        epilogue work, no dense intermediate."""
        return self.encode(vals, idx, length)

    def encode(self, vals, idx, length):
        order = jnp.argsort(idx)
        si = idx[order].astype(jnp.int32)
        sv = vals[order].astype(jnp.float32)
        deltas = jnp.concatenate([si[:1], jnp.diff(si)])
        return {"vals": sv, "deltas": deltas.astype(delta_index_dtype(length))}

    def decode(self, payload, length):
        idx = jnp.cumsum(payload["deltas"].astype(jnp.int32))
        return payload["vals"], idx

    def wire_bits(self, length, k):
        return 32 * k + 8 * jnp.dtype(delta_index_dtype(length)).itemsize * k


def _pack_bits(mask: jax.Array) -> jax.Array:
    """{0,1} mask [L] -> packed uint8 [ceil(L/8)] (little-endian bit order)."""
    L = mask.shape[0]
    pad = (-L) % 8
    m = jnp.pad(mask.astype(jnp.uint8), (0, pad)).reshape(-1, 8)
    weights = (2 ** jnp.arange(8, dtype=jnp.uint8)).astype(jnp.uint8)
    return (m * weights).sum(axis=1).astype(jnp.uint8)


def _unpack_bits(packed: jax.Array, length: int) -> jax.Array:
    bits = (packed[:, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    return bits.reshape(-1)[:length].astype(jnp.float32)


class BitmapDense(Codec):
    """1-bit presence bitmap + values in index-ascending order.

    ``L + 32·k`` bits: wins over COO index lists when S = k/L > 1/32.
    Duplicate padding slots (idx 0, val 0) merge into the bitmap; the value
    vector is order-normalized, so decode returns coordinates ascending.
    """

    name = "bitmap_dense"
    lossless = True
    supports_fused = False  # wire format is inherently dense (RPL105)

    def encode(self, vals, idx, length):
        k = vals.shape[0]
        dense = _scatter_dense(vals.astype(jnp.float32), idx, length)
        mask = jnp.zeros((length,), jnp.float32).at[idx].set(1.0)
        rank = jnp.cumsum(mask).astype(jnp.int32) - 1
        slot = jnp.where(mask > 0, rank, k)  # k is out-of-bounds -> dropped
        packed_vals = (
            jnp.zeros((k,), jnp.float32)
            .at[slot]
            .set(dense, mode="drop")
        )
        return {"bitmap": _pack_bits(mask), "vals": packed_vals}

    def decode(self, payload, length):
        k = payload["vals"].shape[0]
        mask = _unpack_bits(payload["bitmap"], length)
        rank = jnp.cumsum(mask).astype(jnp.int32) - 1
        slot = jnp.where(mask > 0, rank, k)
        idx = (
            jnp.zeros((k,), jnp.int32)
            .at[slot]
            .set(jnp.arange(length, dtype=jnp.int32), mode="drop")
        )
        valid = jnp.arange(k) < mask.sum().astype(jnp.int32)
        return jnp.where(valid, payload["vals"], 0.0), jnp.where(valid, idx, 0)

    def wire_bits(self, length, k):
        return 8 * ((length + 7) // 8) + 32 * k


class CooQ8(Codec):
    """int8 symmetric quantization of the values; indices stay int32.

    Lossy: ``decode`` dequantizes with a per-payload fp32 scale. The caller
    must fold ``vals - decoded`` into the sparsifier's error accumulator so
    error feedback covers the codec (ISSUE tentpole; cf. 1-bit SGD / EF-SGD).
    """

    name = "coo_q8"
    lossless = False
    supports_fused = True

    def encode(self, vals, idx, length):
        # the scale in float32: rounded in a bfloat16 payload's own dtype it
        # can fall below amax / 127 and clip the largest value
        amax = jnp.max(jnp.abs(vals)).astype(jnp.float32)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(vals / scale), -127, 127).astype(jnp.int8)
        return {"q": q, "scale": scale, "idx": idx.astype(jnp.int32)}

    def encode_fused(self, vals, idx, length):
        """Quantization epilogue over the k selected registers: the
        per-payload amax/scale/round chain reads only the fused pipeline's
        output, so it fuses behind the selection kernel with no dense
        intermediate."""
        return self.encode(vals, idx, length)

    def decode(self, payload, length):
        vals = payload["q"].astype(jnp.float32) * payload["scale"]
        return vals, payload["idx"]

    def wire_bits(self, length, k):
        return 8 * k + 32 + 32 * k


CODECS = {
    c.name: c
    for c in (CooFp32(), CooIdxDelta(), BitmapDense(), CooQ8())
}


def get_codec(name: str) -> Codec:
    """Look up a registered codec by name.

    >>> get_codec("bitmap_dense").wire_bits(1024, 16)  # L + 32·k bits
    1536
    >>> get_codec("bogus")
    Traceback (most recent call last):
        ...
    ValueError: unknown codec 'bogus'; available: ['bitmap_dense', \
'coo_fp32', 'coo_idx_delta', 'coo_q8']
    """
    try:
        return CODECS[name]
    except KeyError:
        raise ValueError(
            f"unknown codec {name!r}; available: {sorted(CODECS)}"
        ) from None
