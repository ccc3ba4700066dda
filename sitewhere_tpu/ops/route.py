"""On-device shard routing: radix bucketing + one ICI all_to_all.

The host arena router (parallel/router.py) pays a full host-CPU pass —
stable bucketing sort plus a 5-row gather/scatter — before a single byte
reaches the mesh, and round 5 measured that pass drifting 1.2 -> 6.6 ms
per step under host CPU steal. Routing belongs where the bandwidth is
(the tf.data lesson applied to the shard edge): the feeder ships the
UNROUTED packed blob split into contiguous lane chunks (one chunk per
shard, `P(None, shard)` — pack + one H2D, nothing else on the host), and
the mesh routes it itself inside the same shard_map as the fused step:

  1. bucket: each shard computes its chunk rows' destination shard
     (`dev % S` — the same hash partition the host router and the
     registry interner use), then stable-sorts them into S
     fixed-capacity per-destination lanes via shared sort-rank
     arithmetic (ops/segments.py bucket_ranks): O(B log B), no [B, S]
     one-hot intermediate — the same in-bucket arrival order the old
     one-hot prefix-sum produced, bit for bit.
  2. exchange: ONE `all_to_all` over ICI transposes the [S_dest, C]
     lanes so every shard holds the [S_src, C] buckets destined to it,
     source-major — i.e. flat-batch arrival order.
  3. compact: a prefix-sum over the received candidates' valid bits
     packs them into the local [rows, B] routed blob.

Because the bucketing is stable and the exchange is source-major, the
compacted result is BIT-IDENTICAL to the host arena router's output for
any batch that fits the lanes — every downstream contract (state fold
order, alert-lane contents and order, checkpoint parity) holds exactly,
and the differential tests pin it (tests/test_device_route.py).

Overflow contract: lane capacity is `route_lane_capacity(B, S)` —
2x the uniform per-(source, destination) expectation, capped at B. The
host feeder runs `host_fits_device_route` (two bincount passes, no sort,
no scatter — the cheap 1% of the old host route) before staging; a batch
that would overflow any lane, or any shard's total capacity, spills to
the existing host arena path for that step (bounded fallback, counted on
`device_route_fallbacks` — same philosophy as alert-lane drops: degrade
loudly, never silently). The device kernel still counts any row it had
to drop (belt and braces; zero whenever the guard ran) and rides the
count out on the alert lanes' spare counts slot — no extra D2H fetch.

The packed 3-row wire variant embeds its ts base by LANE POSITION in
row 0 (ops/pack.py): only chunk 0 carries it, so the kernel extracts it
there, broadcasts it with a scalar psum, strips the spare bits before
bucketing (exactly like the host router), and re-embeds per shard after
compaction — bit-for-bit the host `_embed_ts_base` layout.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from sitewhere_tpu.ops.pack import (
    _BASE_LANES, _BASE_SHIFT, _VALID_SHIFT, WIRE_DEV_MAX, WIRE_ROWS_PACKED)

# low-29-bit mask: strips the packed variant's spare ts-base bits from a
# routed head (a no-op on classic 4/5-row blobs, whose spares are zero)
_SPARE_CLEAR = (1 << _BASE_SHIFT) - 1

# the on-device route's defensive drop count rides the alert lanes'
# counts row at this slot (slots 0..2 hold the fired/dropped/total alert
# counts — ops/compact.py; capacity >= MIN_ALERT_LANE_CAPACITY == 4
# guarantees the slot exists). Zero whenever the host guard ran.
ROUTE_DROPPED_SLOT = 3


def route_lane_capacity(per_shard_batch: int, n_shards: int) -> int:
    """Per-(source, destination) lane capacity: 2x the uniform
    expectation ceil(B/S), capped at B. Uniform hash traffic loads each
    lane with mean B/S rows; 2x slack absorbs Poisson fluctuation and
    moderate tenant skew, while the transient lane tensor stays at most
    2x the blob itself ([rows, S, C] vs [rows, B]). Heavier skew is the
    host guard's job (spill the step to the host arena path)."""
    if n_shards <= 1:
        return per_shard_batch
    return min(per_shard_batch, -(-2 * per_shard_batch // n_shards))


def host_fits_device_route(device_idx: np.ndarray, valid: np.ndarray,
                           n_shards: int, per_shard_batch: int,
                           capacity: int) -> bool:
    """Cheap host-side guard: can the device route carry this flat batch
    without dropping a row? True iff every (source chunk, destination)
    bucket fits its lane AND every destination's total fits the
    per-shard batch. Two bincount passes over the shard ids — no sort,
    no scatter; the flat positions are implicit in the chunk slicing, so
    the check costs ~1% of the host arena route it gates."""
    S, B, C = n_shards, per_shard_batch, capacity
    dev = np.asarray(device_idx)
    val = np.asarray(valid)
    n = dev.shape[0]
    shard = (dev % S).astype(np.int64)
    all_valid = bool(val.all())
    totals = np.zeros(S, np.int64)
    for c in range(0, n, B):
        sl = shard[c:c + B]
        if all_valid:
            counts = np.bincount(sl, minlength=S)
        else:
            counts = np.bincount(sl, weights=val[c:c + B],
                                 minlength=S).astype(np.int64)
        if int(counts.max(initial=0)) > C:
            return False
        totals += counts
    return int(totals.max(initial=0)) <= B


# -- jax kernel (call under shard_map) --------------------------------------


def _extract_ts_base(head):
    """jnp mirror of ops.pack._extract_ts_base_np: lift the 32-bit ts
    base from row 0's spare bits, 3 per lane across lanes 0..10 (int32
    shift-left wrap reconstructs lane 10's top bits exactly)."""
    import jax.numpy as jnp

    spare = (head[:_BASE_LANES] >> _BASE_SHIFT) & 7
    base = spare[0]
    for lane in range(1, _BASE_LANES):
        base = base | (spare[lane] << (3 * lane))
    return base.astype(jnp.int32)


def _embed_ts_base(row0, base):
    """jnp mirror of ops.pack._embed_ts_base — bit-identical layout: the
    base is scattered over lanes 0..10 on a uint32 view (LOGICAL shifts,
    so lane 10 carries exactly the top 2 bits, matching the host's
    numpy-uint32 embed even for negative bases)."""
    import jax
    import jax.numpy as jnp

    ubase = jax.lax.bitcast_convert_type(base, jnp.uint32)
    lanes = jax.lax.bitcast_convert_type(row0[:_BASE_LANES], jnp.uint32)
    shifts = jnp.uint32(3) * jnp.arange(_BASE_LANES, dtype=jnp.uint32)
    bits = (ubase >> shifts) & jnp.uint32(7)
    lanes = lanes | (bits << jnp.uint32(_BASE_SHIFT))
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(lanes, jnp.int32),
         row0[_BASE_LANES:]])


def device_route_chunk(chunk, n_shards: int, per_shard_batch: int,
                       capacity: int, axis_name: str):
    """Route this shard's unrouted lane chunk to its owner shards.

    `chunk` is the [wire_rows, B] contiguous slice of the flat wire blob
    this shard received (flat lanes [i*B, (i+1)*B) for shard i — flat
    arrival order). Returns (routed [wire_rows, B] blob for THIS shard,
    rows this shard had to drop), where the blob is bit-identical to the
    host arena router's per-shard output whenever nothing dropped. Call
    under shard_map on `axis_name`; contains one all_to_all (plus one
    scalar psum for the packed wire variant's ts base).
    """
    import jax
    import jax.numpy as jnp

    S, B, C = n_shards, per_shard_batch, capacity
    rows = chunk.shape[0]
    packed = rows == WIRE_ROWS_PACKED
    head = chunk[0]
    if packed:
        # only chunk 0 carries the lane-embedded base: lift it there and
        # broadcast (a 4-byte psum — noise next to the row exchange)
        base_local = jnp.where(
            jax.lax.axis_index(axis_name) == 0, _extract_ts_base(head),
            jnp.int32(0))
        base = jax.lax.psum(base_local, axis_name)
    from sitewhere_tpu.ops.segments import bucket_ranks

    valid = (head >> _VALID_SHIFT) & 1
    dev = head & (WIRE_DEV_MAX - 1)
    dest = jnp.where(valid == 1, dev % S, S)          # S = padding sentinel
    # stable sort-based bucketing: rank of each row within its
    # destination bucket via one shared stable sort + segment-start
    # subtraction — O(B log B), no [B, S] one-hot intermediate. Invalid
    # rows (sentinel bucket S) get real ranks but `keep` masks them out
    # exactly like the old counting sort's rank -1.
    pos = bucket_ranks(dest)
    keep = (valid == 1) & (pos < C)
    slot = jnp.where(keep, dest * C + pos, S * C)      # OOB -> dropped
    # routed heads carry LOCAL device indices with spare bits stripped,
    # exactly like the host router's head rewrite
    local_head = ((head & _SPARE_CLEAR & ~jnp.int32(WIRE_DEV_MAX - 1))
                  | (dev // S))
    # one [rows, B] -> [rows, S*C] scatter builds every wire row's lane
    # at once (unique slots; OOB rows drop)
    vals = jnp.concatenate([local_head[None], chunk[1:]], axis=0)
    lanes = jnp.zeros((rows, S * C), jnp.int32).at[:, slot].set(
        vals, mode="drop")                             # [rows, S*C]
    dropped = jnp.sum(((valid == 1) & ~keep).astype(jnp.int32))
    # ONE collective: transpose the per-destination lanes so this shard
    # holds every source's bucket for it, source-major (= arrival order)
    recv = jax.lax.all_to_all(lanes.reshape(rows, S, C), axis_name,
                              split_axis=1, concat_axis=1)
    cand = recv.reshape(rows, S * C)
    cvalid = (cand[0] >> _VALID_SHIFT) & 1
    crank = jnp.cumsum(cvalid) - cvalid                # exclusive rank
    ckeep = (cvalid == 1) & (crank < B)
    cslot = jnp.where(ckeep, crank, B)                 # OOB -> dropped
    blob = jnp.zeros((rows, B), jnp.int32).at[:, cslot].set(
        cand, mode="drop")
    dropped = dropped + jnp.sum(((cvalid == 1) & ~ckeep).astype(jnp.int32))
    if packed:
        blob = blob.at[0].set(_embed_ts_base(blob[0], base))
    return blob, dropped


def build_device_route_program(mesh, n_shards: int, per_shard_batch: int,
                               capacity: Optional[int] = None):
    """Standalone jitted route-only program over `mesh`: flat wire blob
    [wire_rows, S*B] (lane-sharded `P(None, shard)`) -> (routed
    [S, wire_rows, B] global array, per-shard dropped counts [S]).

    The differential tests compare its output against
    `ShardRouter.route_blob` bit for bit, and the bench's pinned
    `router_offload_speedup_x` micro-bench times it against the host
    arena route at full batch. The engine's fused step uses
    `device_route_chunk` directly inside its own shard_map instead."""
    import jax
    from jax.sharding import PartitionSpec as P

    from sitewhere_tpu.parallel.mesh import SHARD_AXIS

    cap = capacity or route_lane_capacity(per_shard_batch, n_shards)

    def route(flat_blob):
        blob, dropped = device_route_chunk(
            flat_blob, n_shards, per_shard_batch, cap, SHARD_AXIS)
        return blob[None], dropped[None]

    specs = dict(mesh=mesh, in_specs=P(None, SHARD_AXIS),
                 out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)))
    return jax.jit(jax.shard_map(route, check_vma=False, **specs))
