"""HBM-resident hash tables over 128-byte wire-layout rows — straight-line probes.

This is the TPU-native replacement for the reference's Groove object store +
CacheMap (reference: src/lsm/groove.zig:602-760, src/lsm/cache_map.zig): the
full working set lives in HBM as a single [capacity + 1, 32] u32 array per
table, each row being the object's 128-byte little-endian wire format
(reference: src/tigerbeetle.zig:7-104) — so a host batch uploads as one
bitcast and a probe fetches a whole object in one gather.

Design constraints discovered on the target stack (and why this file has NO
lax.while_loop / lax.cond / data-dependent trip counts):

- Plain gathers/scatters over multi-GiB tables are fast (~30us for an
  8k-lane batch), including window gathers of [B, W, 4] probe keys.
- A gather INSIDE a while_loop/scan body permanently degrades the process's
  dispatch path (every subsequent kernel launch ~12ms instead of ~30us) —
  measured, reproducible, and fatal for throughput. Data-dependent probe
  continuation loops are therefore banned from every device kernel.

So probing is **double hashing with a fixed probe window**: probe j visits
`(h1(key) + j * step(key)) & mask` with `step` odd (coprime to the power-of-2
capacity, so the sequence visits every slot). All W probes for all lanes are
fetched in ONE window gather and resolved branch-free. Double hashing (vs
linear probing) makes chain-length tails geometric with NO clustering:
P(chain >= W) ~ alpha^W, so with the enforced load factor alpha <= 1/2
(constants.LOAD_FACTOR_*) and W = 32, an unresolved probe is a ~2^-32 event
per op. Unresolved lanes are reported to the caller, which must abort the
whole batch (no partial application) and raise a sticky fault — see
models/ledger.py's fault protocol.

Key encoding in row words 0..3 (the id):
- empty slot:     all four words 0  (valid ids are never 0)
- tombstone slot: all four words 0xFFFFFFFF  (valid ids are never u128 max;
  both invariants enforced by id_must_not_be_zero / id_must_not_be_int_max,
  reference: src/tigerbeetle.zig:118-121, 160-163)
Tombstones arise only from linked-chain rollback deletions: probes skip them
(only an EMPTY slot terminates a chain), inserts reuse them.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

U64 = jnp.uint64
U32 = jnp.uint32
I32 = jnp.int32

# NOTE: module-level constants MUST be numpy (not jnp): numpy scalars embed
# as XLA literals, where a jitted function that captures a concrete jax
# array holds a device buffer (and creating one at import would initialise
# a backend — taking the chip — in every process that imports this module).
# On an earlier rig a captured buffer also slowed every later launch
# (~30 us -> ~12 ms); chip_smoke.py's `probe` phase re-measures dispatch on
# the machine it runs on (see PERF.md).
TOMB_WORD = np.uint32(0xFFFFFFFF)
CLAIM_FREE = np.uint32(0xFFFFFFFF)

_MIX = np.uint64(0x9E3779B97F4A7C15)
_MIX2 = np.uint64(0xD1B54A32D192ED03)

# Fixed probe windows. Batched table ops probe WINDOW slots in one gather;
# scalar probes (the serial scan kernel) use the longer WINDOW_SCALAR prefix
# of the same probe sequence — a longer window is near-free for one lane and
# makes a serial-tier unresolved probe (which cannot be rolled back mid-scan)
# a ~2^-64 event.
WINDOW = 32
WINDOW_SCALAR = 64


def key4_of_rows(rows):
    """The id words of wire rows (works for [N, 32] and [32])."""
    return rows[..., :4]


def _fold64(key4):
    k = key4.astype(U64)
    lo = k[..., 0] | (k[..., 1] << jnp.uint64(32))
    hi = k[..., 2] | (k[..., 3] << jnp.uint64(32))
    return lo, hi


def hash_key4(key4, cap_log2: int):
    """splitmix64 finalizer over both id limbs -> base slot in [0, 2^cap_log2)."""
    lo, hi = _fold64(key4)
    x = lo ^ (hi * _MIX)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    x = x ^ (x >> jnp.uint64(31))
    return (x & jnp.uint64((1 << cap_log2) - 1)).astype(I32)


def probe_step(key4, cap_log2: int):
    """Second, independent hash -> ODD probe stride (odd strides are units
    mod 2^cap_log2, so the probe sequence is a full cycle)."""
    lo, hi = _fold64(key4)
    x = (lo ^ jnp.uint64(0x6A09E667F3BCC909)) * _MIX2
    x = x ^ (hi * _MIX2) ^ (x >> jnp.uint64(31))
    x = (x ^ (x >> jnp.uint64(29))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = x ^ (x >> jnp.uint64(32))
    return ((x & jnp.uint64((1 << cap_log2) - 1)) | jnp.uint64(1)).astype(I32)


def probe_positions(key4, cap_log2: int, window: int):
    """[..., window] i32 slots: the first `window` probes of key4's sequence."""
    mask = jnp.int32((1 << cap_log2) - 1)
    base = hash_key4(key4, cap_log2)
    step = probe_step(key4, cap_log2)
    j = jnp.arange(window, dtype=I32)
    return (base[..., None] + j * step[..., None]) & mask


def _is_empty(k4):
    return jnp.all(k4 == 0, axis=-1)


def _is_tomb(k4):
    return jnp.all(k4 == TOMB_WORD, axis=-1)


def occupied_mask(rows):
    """Per-slot liveness of a [N, 32] row table: neither empty nor
    tombstone (THE definition — spill scans and query filter scans must
    agree bit-for-bit with the probe kernels' slot encoding)."""
    k4 = rows[..., :4]
    return ~_is_empty(k4) & ~_is_tomb(k4)


def lookup(key4, rows, cap_log2: int, window: int = WINDOW):
    """Probe for key4 ([..., 4] u32; batched or scalar). ONE window gather,
    branch-free resolve. Returns (slot i32, found bool, resolved bool):

    - found: the key is in the table; `slot` is its row.
    - not found but resolved: an EMPTY slot terminated the chain before any
      hit; `slot` is the first free (empty or tombstone) probe position —
      the insert target for this key.
    - not resolved (~2^-window per op at load <= 1/2): no hit and no empty
      within the window; `slot` is arbitrary. The CALLER must treat the
      whole batch as failed (fault protocol) — results are unsound.

    Keys that are themselves empty/tomb-encoded (all-0s / all-1s ids) are
    never reported found; they resolve like ordinary absent keys.
    """
    pos = probe_positions(key4, cap_log2, window)  # [..., W]
    k4 = rows[pos, :4]  # [..., W, 4]
    key_probeable = ~_is_empty(key4) & ~_is_tomb(key4)
    hit = jnp.all(k4 == key4[..., None, :], axis=-1) & key_probeable[..., None]
    empty = _is_empty(k4)
    free = empty | _is_tomb(k4)

    j = jnp.arange(window, dtype=I32)
    big = jnp.int32(window)
    hit_j = jnp.min(jnp.where(hit, j, big), axis=-1)
    empty_j = jnp.min(jnp.where(empty, j, big), axis=-1)
    free_j = jnp.min(jnp.where(free, j, big), axis=-1)

    found = hit_j < empty_j  # a hit before the chain terminator
    resolved = found | (empty_j < big)
    sel = jnp.where(found, hit_j, jnp.minimum(free_j, big - 1))
    slot = jnp.take_along_axis(pos, sel[..., None], axis=-1)[..., 0]
    return slot, found, resolved


def claim_slots(key4, active, rows, claim, cap_log2: int,
                window: int = WINDOW, rounds: int = 4):
    """Claim one distinct free slot per active lane for batch-unique, absent
    keys (the parallel-insert slot assignment). Pure claim phase: the rows
    table is NOT written — the caller scatters the rows after gating on
    `resolved` (so an aborting batch leaves the table untouched).

    Returns (slots i32 [B], claim', resolved bool [B]). `slots` is the dump
    slot (capacity) for inactive or unresolved lanes. `claim` is the
    persistent [capacity+1] u32 scratch column (CLAIM_FREE everywhere between
    batches); claims are held across rounds as in-batch occupancy and all
    released before return.

    Races between lanes probing the same slot are resolved deterministically
    by scatter-min of the lane index; a losing lane's next round recomputes
    its first free-and-unclaimed probe position (the lost slot is now
    claimed, so it is skipped automatically). With double hashing, two lanes
    share more than one probe position only on a ~2^-64 hash collision, so
    `rounds` bounds the CONTENTION depth, not chain length; unresolved lanes
    after `rounds` rounds are reported, not retried.
    """
    cap = 1 << cap_log2
    dump = jnp.int32(cap)
    B = key4.shape[0]
    lanes = jnp.arange(B, dtype=U32)

    pos = probe_positions(key4, cap_log2, window)  # [B, W]
    k4 = rows[pos, :4]
    table_free = _is_empty(k4) | _is_tomb(k4)  # [B, W] — static during claims

    j = jnp.arange(window, dtype=I32)
    big = jnp.int32(window)

    won = jnp.zeros(B, dtype=bool)
    slot = jnp.full(B, dump, dtype=I32)
    for _ in range(rounds):
        clm_w = claim[pos]  # [B, W] — refreshed each round
        cand_j = jnp.min(
            jnp.where(table_free & (clm_w == CLAIM_FREE), j, big), axis=-1
        )
        has_cand = cand_j < big
        cand = jnp.take_along_axis(
            pos, jnp.minimum(cand_j, big - 1)[:, None], axis=-1
        )[:, 0]
        want = active & ~won & has_cand
        tgt = jnp.where(want, cand, dump)
        claim = claim.at[tgt].min(lanes)
        newly = want & (claim[cand] == lanes)
        slot = jnp.where(newly, cand, slot)
        won = won | newly

    resolved = won | ~active
    # Release every claim this batch made: winners' slots + the dump slot
    # (losing lanes' scatter-min landed on slots that some lane won, or on
    # the dump slot — both covered).
    claim = claim.at[slot].set(CLAIM_FREE).at[dump].set(CLAIM_FREE)
    return slot, claim, resolved


def probe_free(key4, rows, cap_log2: int, window: int = WINDOW_SCALAR):
    """First free (empty or tombstone) probe position for a key known to be
    absent (the serial scan kernel's insert target; it masks its own writes).
    Returns (slot, ok). One window gather, no loops."""
    pos = probe_positions(key4, cap_log2, window)
    k4 = rows[pos, :4]
    free = _is_empty(k4) | _is_tomb(k4)
    j = jnp.arange(window, dtype=I32)
    big = jnp.int32(window)
    free_j = jnp.min(jnp.where(free, j, big), axis=-1)
    ok = free_j < big
    sel = jnp.minimum(free_j, big - 1)
    slot = jnp.take_along_axis(pos, sel[..., None], axis=-1)[..., 0]
    return slot, ok


def insert_rows(row32, active, rows, claim, cap_log2: int,
                window: int = WINDOW, rounds: int = 4):
    """claim_slots + row scatter in one call (convenience for callers that
    gate on `resolved` themselves AFTER the write — e.g. test harnesses).
    Production kernels should use claim_slots and scatter after gating.

    Returns (slots, rows', claim', resolved)."""
    key4 = key4_of_rows(row32)
    slots, claim, resolved = claim_slots(
        key4, active, rows, claim, cap_log2, window=window, rounds=rounds
    )
    rows = rows.at[slots].set(row32)
    return slots, rows, claim, resolved
