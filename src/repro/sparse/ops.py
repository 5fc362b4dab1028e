"""Segment-op primitives used across the framework.

All ops are shape-static and jit/vmap/shard_map friendly. Padding convention:
invalid entries carry ``segment_id == num_segments`` (one past the end) and are
dropped by passing ``num_segments + 1`` internally and slicing the tail off, or
by masking values to the reduction identity.

Packed-key fast path (fused AWAC sweep engine, DESIGN.md §3): when 64-bit
types are available at trace time (``jax.enable_x64(True)`` entered
around the jitted call), the two-reduction argmax-with-tie-break ops below
collapse into a single ``segment_max`` over a packed uint64 key
``f32-key-bits ⧺ bitwise-not(payload)``, halving the number of O(m) scatter
passes while staying bit-identical to the two-pass reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -jnp.inf

_SIGN32 = np.int32(np.uint32(0x80000000))


def x64_available() -> bool:
    """True when 64-bit dtypes survive canonicalization in the current trace
    context (i.e. we are under ``jax.enable_x64(True)``)."""
    return jax.dtypes.canonicalize_dtype(np.uint64).itemsize == 8


def _f32_sort_key(values):
    """Monotone int32 key for float32 totally ordered like the floats
    (-inf < ... < +inf; -0.0 and +0.0 compare in bit order — callers only
    feed gains, never signed zeros that must tie)."""
    bits = jax.lax.bitcast_convert_type(values, jnp.int32)
    return jnp.where(bits < 0, ~bits, bits ^ _SIGN32)


def _f32_from_sort_key(key):
    bits = jnp.where(key < 0, key ^ _SIGN32, ~key)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _packed_segment_max(values, payload, segment_ids, num_segments):
    """One-pass (max value, min payload) per segment via a packed uint64 key.

    Requires an x64-enabled trace context. ``payload`` must be >= 0 int32.
    Returns (seg_max f32, seg_payload i32) with (-inf, -1) for empty segments
    and payload -1 wherever seg_max == -inf (matching the two-pass reference).
    """
    key_hi = _f32_sort_key(values)
    # ~payload: smaller payload -> larger low word -> wins uint64 max on ties.
    pair = jnp.stack([~payload, key_hi], axis=-1)  # little-endian: low first
    key = jax.lax.bitcast_convert_type(pair, jnp.uint64)
    out = jax.ops.segment_max(key, segment_ids, num_segments=num_segments)
    pair_out = jax.lax.bitcast_convert_type(out, jnp.uint32).astype(jnp.int32)
    k_hi = pair_out[..., 1]
    seg_payload = ~pair_out[..., 0]
    # uint64 identity (0) only decodes from the impossible (NaN key, payload
    # -1) combination, so it identifies empty segments exactly.
    empty = (k_hi == 0) & (pair_out[..., 0] == 0)
    seg_max = jnp.where(empty, NEG, _f32_from_sort_key(k_hi))
    seg_payload = jnp.where(empty | (seg_max == NEG), -1, seg_payload)
    return seg_max, seg_payload


def segment_max_with_payload(values, payload, segment_ids, num_segments):
    """Per-segment max of ``values`` and the payload of (one of) the argmax rows.

    Ties are broken toward the smallest payload value, which makes the result
    deterministic (the paper's Step C/D pick "one with maximum gain"; we fix the
    tie-break so sequential and distributed implementations agree bit-for-bit).

    Returns (seg_max [num_segments], seg_payload [num_segments int32]).
    Segments with no entries get (-inf, -1).

    Under an x64-enabled trace this is a single packed-key ``segment_max``
    pass; otherwise the two-reduction reference below runs. Both produce
    bit-identical results (see tests/test_fused_sweep.py).
    """
    if x64_available():
        return _packed_segment_max(values, payload, segment_ids, num_segments)
    seg_max = jax.ops.segment_max(
        values, segment_ids, num_segments=num_segments, indices_are_sorted=False
    )
    # Rows achieving their segment's max; among them take min payload.
    hit = values == seg_max[jnp.clip(segment_ids, 0, num_segments - 1)]
    big = jnp.iinfo(jnp.int32).max
    cand = jnp.where(hit, payload, big)
    seg_payload = jax.ops.segment_min(cand, segment_ids, num_segments=num_segments)
    seg_payload = jnp.where(seg_max == NEG, -1, seg_payload)
    seg_payload = jnp.where(seg_payload == big, -1, seg_payload)
    return seg_max, seg_payload


def segment_argmax_tie(values, tie, segment_ids, num_segments):
    """Per-segment argmax with an explicit tie-break key (smallest ``tie``
    wins; a second tie falls back to smallest index). Returns
    (seg_max, seg_idx) where seg_idx indexes into ``values`` (-1 if empty).

    Used by the distributed AWAC Step C so that the distributed winner
    selection matches the single-device rule (max gain, tie -> smallest row)
    even though edges arrive in a different order.

    Under an x64-enabled trace the (max, tie) reduction is one packed-key
    pass + one index-recovery pass instead of three segment reductions."""
    big = jnp.iinfo(jnp.int32).max
    idx = jnp.arange(values.shape[0], dtype=jnp.int32)
    if x64_available():
        seg_max, seg_tie = _packed_segment_max(
            values, tie, segment_ids, num_segments
        )
        hit2 = (values == seg_max[jnp.clip(segment_ids, 0, num_segments - 1)]) & (
            tie == seg_tie[jnp.clip(segment_ids, 0, num_segments - 1)]
        )
        idx_m = jnp.where(hit2, idx, big)
        seg_idx = jax.ops.segment_min(idx_m, segment_ids, num_segments=num_segments)
        seg_idx = jnp.where((seg_max == NEG) | (seg_idx == big), -1, seg_idx)
        return seg_max, seg_idx
    seg_max = jax.ops.segment_max(values, segment_ids, num_segments=num_segments)
    hit = values == seg_max[jnp.clip(segment_ids, 0, num_segments - 1)]
    tie_m = jnp.where(hit, tie, big)
    seg_tie = jax.ops.segment_min(tie_m, segment_ids, num_segments=num_segments)
    hit2 = hit & (tie == seg_tie[jnp.clip(segment_ids, 0, num_segments - 1)])
    idx_m = jnp.where(hit2, idx, big)
    seg_idx = jax.ops.segment_min(idx_m, segment_ids, num_segments=num_segments)
    seg_idx = jnp.where((seg_max == NEG) | (seg_idx == big), -1, seg_idx)
    return seg_max, seg_idx


def batched_segment_max_with_payload(values, payload, segment_ids, num_segments):
    """Batched ``segment_max_with_payload``: values/payload/segment_ids are
    [B, m], segments are per-instance (ids in [0, num_segments]), and the
    reduction runs as ONE flat segment op over B * (num_segments + 1)
    offset segments instead of B dispatches or a vmapped scatter.

    Payloads stay *local* (per-instance edge indices), so the smallest-payload
    tie-break picks the same winner as a per-instance call — the batched
    engine (core/batch.py) relies on this for bit-exactness with core.single.
    Returns (seg_max [B, num_segments], seg_payload [B, num_segments])."""
    b, m = values.shape
    stride = num_segments + 1  # room for the per-instance dump segment
    offs = (jnp.arange(b, dtype=segment_ids.dtype) * stride)[:, None]
    flat_seg = (segment_ids + offs).reshape(-1)
    seg_max, seg_payload = segment_max_with_payload(
        values.reshape(-1), payload.reshape(-1), flat_seg, b * stride
    )
    seg_max = seg_max.reshape(b, stride)[:, :num_segments]
    seg_payload = seg_payload.reshape(b, stride)[:, :num_segments]
    return seg_max, seg_payload


def batched_segment_argmax_tie(values, tie, segment_ids, num_segments):
    """Batched ``segment_argmax_tie``: values/tie/segment_ids are [B, m] with
    per-instance segments, flattened to one offset-segment reduction (same
    layout contract as ``batched_segment_max_with_payload``). Returned
    seg_idx is *local* (an index into instance b's own [m] row; -1 if
    empty) — within an instance the smallest flat index is the smallest
    local index, so the final-level tie-break matches a per-instance call.
    Returns (seg_max [B, num_segments], seg_idx [B, num_segments])."""
    b, m = values.shape
    stride = num_segments + 1
    offs = (jnp.arange(b, dtype=segment_ids.dtype) * stride)[:, None]
    seg_max, seg_idx = segment_argmax_tie(
        values.reshape(-1), tie.reshape(-1), (segment_ids + offs).reshape(-1),
        b * stride,
    )
    seg_max = seg_max.reshape(b, stride)[:, :num_segments]
    seg_idx = seg_idx.reshape(b, stride)[:, :num_segments]
    row_offs = (jnp.arange(b, dtype=seg_idx.dtype) * m)[:, None]
    return seg_max, jnp.where(seg_idx >= 0, seg_idx - row_offs, -1)


def batched_segment_min(values, segment_ids, num_segments):
    """Batched ``jax.ops.segment_min`` over per-instance segments, flattened
    to one offset-segment reduction (same layout contract as
    ``batched_segment_max_with_payload``). Returns [B, num_segments]."""
    b, m = values.shape
    stride = num_segments + 1
    offs = (jnp.arange(b, dtype=segment_ids.dtype) * stride)[:, None]
    out = jax.ops.segment_min(
        values.reshape(-1), (segment_ids + offs).reshape(-1),
        num_segments=b * stride,
    )
    return out.reshape(b, stride)[:, :num_segments]


@functools.partial(jax.jit, static_argnames=("n_steps",))
def batched_searchsorted_in_window(keys, q, lo, hi, n_steps: int):
    """Batched ``searchsorted_in_window``: keys are [B, m]; q/lo/hi are
    [B, k] (k queries per instance, windows in per-instance coordinates).
    Flattens to one search over [B * m] keys by offsetting each instance's
    windows by b * m — windows never cross instance boundaries, so every
    probe reads the same key the per-instance search would. Returns
    (pos [B, k] local, found [B, k])."""
    b, m = keys.shape
    offs = (jnp.arange(b, dtype=lo.dtype) * m)[:, None]
    pos, found = searchsorted_in_window(
        keys.reshape(-1), q.reshape(-1), (lo + offs).reshape(-1),
        (hi + offs).reshape(-1), n_steps=n_steps,
    )
    return pos.reshape(q.shape) - offs, found.reshape(q.shape)


def segment_argmax(values, segment_ids, num_segments):
    """Per-segment argmax (row index into ``values``); -1 for empty segments."""
    idx = jnp.arange(values.shape[0], dtype=jnp.int32)
    _, arg = segment_max_with_payload(values, idx, segment_ids, num_segments)
    return arg


def segment_softmax(logits, segment_ids, num_segments):
    """Numerically-stable softmax within each segment (GAT-style edge softmax)."""
    seg_max = jax.ops.segment_max(logits, segment_ids, num_segments=num_segments)
    seg_max = jnp.where(jnp.isneginf(seg_max), 0.0, seg_max)
    shifted = logits - seg_max[segment_ids]
    ex = jnp.exp(shifted)
    denom = jax.ops.segment_sum(ex, segment_ids, num_segments=num_segments)
    return ex / jnp.maximum(denom[segment_ids], 1e-30)


def coo_spmm(row, col, val, x, n_rows):
    """y = A @ x for COO A (row, col, val) and dense x [n_cols, d].

    Padding entries must have ``row == n_rows`` (they are accumulated into a
    scratch segment and dropped). This is the GNN message-passing primitive.
    """
    msgs = jnp.take(x, col, axis=0) * val[:, None]
    y = jax.ops.segment_sum(msgs, row, num_segments=n_rows + 1)
    return y[:n_rows]


def coo_sddmm(row, col, a, b):
    """Sampled dense-dense matmul: out[e] = <a[row[e]], b[col[e]]>."""
    return jnp.einsum(
        "ed,ed->e", jnp.take(a, row, axis=0), jnp.take(b, col, axis=0)
    )


@functools.partial(jax.jit, static_argnames=("n_steps",))
def lex_searchsorted(keys_r, keys_c, q_r, q_c, n_steps: int = 32):
    """Vectorized fixed-depth binary search for (q_r, q_c) in the lexicographically
    sorted key pairs (keys_r, keys_c). Returns (pos, found) where ``pos`` is the
    insertion index and ``found`` marks exact hits.

    Avoids int64 key encoding (row*ncols+col overflows int32 for big blocks);
    n_steps=32 covers any int32-sized array.
    """
    m = keys_r.shape[0]
    lo = jnp.zeros_like(q_r)
    hi = jnp.full_like(q_r, m)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) // 2
        mid_c = jnp.clip(mid, 0, m - 1)
        kr = keys_r[mid_c]
        kc = keys_c[mid_c]
        # key < query (lexicographic)
        lt = (kr < q_r) | ((kr == q_r) & (kc < q_c))
        lo = jnp.where(lt, mid + 1, lo)
        hi = jnp.where(lt, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, n_steps, body, (lo, hi))
    pos = lo
    pos_c = jnp.clip(pos, 0, m - 1)
    found = (pos < m) & (keys_r[pos_c] == q_r) & (keys_c[pos_c] == q_c)
    return pos, found


@functools.partial(jax.jit, static_argnames=("n_steps",))
def searchsorted_in_window(keys, q, lo, hi, n_steps: int):
    """Per-query binary search for ``q`` inside the sorted window
    ``keys[lo:hi)`` (CSR-windowed completion lookup, DESIGN.md §3).

    ``n_steps`` must cover the widest window (ceil(log2(max_width)) + 1);
    with CSR row windows that is the max row degree — log2(nnz/n)-ish rounds
    instead of the log2(m) a global lex search needs. Returns (pos, found).
    """
    m = keys.shape[0]
    hi0 = hi

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) // 2
        k = keys[jnp.clip(mid, 0, m - 1)]
        lt = k < q
        lo = jnp.where(lt, mid + 1, lo)
        hi = jnp.where(lt, hi, mid)
        return lo, hi

    lo, _ = jax.lax.fori_loop(0, n_steps, body, (lo, hi0))
    pos = lo
    found = (pos < hi0) & (keys[jnp.clip(pos, 0, m - 1)] == q)
    return pos, found
