"""Pallas TPU kernels — the ops where a hand-written kernel beats XLA.

Scope note (deliberate, hardware-driven): the acquire hot path is random
gather/scatter over an HBM-resident table. XLA lowers those to the TPU's
native dynamic-(update)-slice hardware path; Mosaic/Pallas exposes no
scatter primitive at all and only a 2D gather, and any dense one-hot
reformulation is O(B·N) — profitable only when gathering >= 128 features
per row (embedding tables), not 3 scalars. So the per-batch decision kernel
stays on XLA (see ``kernels.acquire_batch_packed``), and Pallas is used
where it actually wins: **streaming whole-table passes**, which are
HBM-bandwidth-bound and fuse naturally.

:func:`sweep_expired_pallas` is the TTL eviction pass (SURVEY.md invariant
5) as one fused streaming kernel:

- reads ``tokens``/``last_ts``/``exists`` once, tile by tile;
- computes the expiry predicate (idle past time-to-full TTL, clamped
  ``[1s, 1yr]`` — ``RedisTokenBucketRateLimiter.cs:234-235``);
- clears ``exists`` in place for expired slots;
- emits a **per-tile expired count** alongside the mask, accumulated in
  SMEM across the sequential TPU grid.

The count vector is tiny (N/TILE int32), so the host can decide whether a
10M-slot sweep freed anything by fetching ~KBs instead of a 10 MB bool
mask: a no-op sweep costs one small readback, not a bulk transfer.

The caller picks the mode: compiled Mosaic on a TPU (the store's only
sweep there; a failure raises), or ``interpret=True``, which runs the
same kernel in Pallas's interpreter on the CPU for the tests. Nothing
here falls back on its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributedratelimiting.redis_tpu.ops import bucket_math as bm

__all__ = ["sweep_expired_pallas", "LANES", "SUBLANES"]

LANES = 128      # TPU lane count — last dim of every tile
SUBLANES = 8     # f32 sublane count — second-to-last dim granularity
TILE_ROWS = 256  # rows of 128 lanes per grid step (32K slots, 384 KB VMEM)


def _sweep_kernel(now_ref, cap_ref, rate_ref, tokens_ref, last_ts_ref,
                  exists_ref, exists_out_ref, mask_ref, counts_ref):
    """One grid step: TTL-expire one [TILE_ROWS, 128] tile."""
    now = now_ref[0]
    capacity = cap_ref[0]
    rate = rate_ref[0]

    tokens = tokens_ref[:]
    last_ts = last_ts_ref[:]
    exists = exists_ref[:]

    # time_to_full_ttl, inlined on the VPU (same math as bucket_math).
    deficit = jnp.maximum(capacity - tokens, 0.0)
    ttl = jnp.ceil(deficit / jnp.maximum(rate, 1e-30))
    ttl = jnp.clip(ttl, bm.MIN_TTL_TICKS,
                   min(bm.MAX_TTL_TICKS, 2**31 - 1)).astype(jnp.int32)
    elapsed = jnp.maximum(0, now - last_ts)
    expired = (exists != 0) & (elapsed >= ttl)

    exists_out_ref[:] = jnp.where(expired, 0, exists).astype(jnp.int8)
    mask_ref[:] = expired.astype(jnp.int8)
    # One count per grid step, broadcast over a minimum-size (8, 128) vector
    # tile (the host reads element [0, 0] of each step's tile).
    counts_ref[:] = jnp.broadcast_to(
        jnp.sum(expired.astype(jnp.int32)), (SUBLANES, LANES)
    )


@functools.partial(jax.jit, donate_argnums=(2,),
                   static_argnames=("interpret",))
def sweep_expired_pallas(tokens, last_ts, exists_i8, now, capacity,
                         fill_rate_per_tick, *, interpret: bool = False):
    """Fused streaming TTL sweep over the whole table.

    Args:
      tokens: f32[N] token balances, N a multiple of ``TILE_ROWS * LANES``
        is NOT required — inputs are padded here (padding rows carry
        ``exists = 0`` so they can never count as expired).
      last_ts: i32[N]; exists_i8: i8[N] (0/1 occupancy — int8 keeps the
        occupancy traffic and mask readback at 1 byte/slot). ``exists_i8``
        is **donated**: its buffer is aliased to ``new_exists`` so the
        occupancy plane is not double-buffered during a full-table sweep
        (1 byte/slot — 10 MB transient at 10M slots; drl-xla
        ``xla-donation`` pins the alias in the lowered artifact). Callers
        pass a fresh array (every call site builds one via ``astype``)
        and must not reuse it after the call. ``tokens``/``last_ts`` are
        read-only here and stay un-donated — the caller keeps them.
      now/capacity/fill_rate_per_tick: scalars (host-side Python/np values
        or 0-d arrays).

    Returns:
      ``(new_exists i8[N], expired_mask i8[N], tile_counts i32[T])`` where
      ``T = ceil(N / (TILE_ROWS*LANES))``. ``tile_counts.sum() == 0`` means
      the sweep freed nothing — a decision the host reaches by reading T
      ints, not N bytes.
    """
    n = tokens.shape[0]
    tile = TILE_ROWS * LANES
    t = -(-n // tile)
    padded = t * tile
    if padded != n:
        pad = padded - n
        tokens = jnp.concatenate([tokens, jnp.zeros((pad,), tokens.dtype)])
        last_ts = jnp.concatenate([last_ts, jnp.zeros((pad,), last_ts.dtype)])
        exists_i8 = jnp.concatenate(
            [exists_i8, jnp.zeros((pad,), exists_i8.dtype)])

    tokens2 = tokens.reshape(t * TILE_ROWS, LANES)
    last2 = last_ts.reshape(t * TILE_ROWS, LANES)
    exists2 = exists_i8.reshape(t * TILE_ROWS, LANES)

    now_arr = jnp.asarray(now, jnp.int32).reshape(1)
    cap_arr = jnp.asarray(capacity, jnp.float32).reshape(1)
    rate_arr = jnp.asarray(fill_rate_per_tick, jnp.float32).reshape(1)

    tile_spec = pl.BlockSpec((TILE_ROWS, LANES), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    scalar_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    new_exists2, mask2, counts = pl.pallas_call(
        _sweep_kernel,
        grid=(t,),
        in_specs=[scalar_spec, scalar_spec, scalar_spec,
                  tile_spec, tile_spec, tile_spec],
        out_specs=[
            tile_spec,
            tile_spec,
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t * TILE_ROWS, LANES), jnp.int8),
            jax.ShapeDtypeStruct((t * TILE_ROWS, LANES), jnp.int8),
            jax.ShapeDtypeStruct((t * SUBLANES, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(now_arr, cap_arr, rate_arr, tokens2, last2, exists2)

    return (new_exists2.reshape(-1)[:n], mask2.reshape(-1)[:n],
            counts[::SUBLANES, 0])
