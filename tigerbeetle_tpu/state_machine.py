"""StateMachine: the wire-facing execution interface the VSR layer drives.

This is the TPU build's analog of the reference's StateMachine lifecycle
(reference: src/state_machine.zig:336-540 prepare/commit and :208-214 the
operation enum): one entry point accepts an operation (128-131) plus the
prepare's body bytes, and returns the reply body bytes in the reference's
wire encoding:

- create_accounts / create_transfers: sparse ``{index: u32, result: u32}``
  result structs, only non-ok entries, chain rollbacks in FIFO order
  (reference: src/tigerbeetle.zig:231-249, src/state_machine.zig:612-698).
- lookup_accounts / lookup_transfers: the found objects' 128-byte wire rows,
  in request order, missing ids skipped (reference:
  src/state_machine.zig:701-736).

The backend is anything with the ledger driver API (execute_dense /
prepare / lookup_* — device backends also expose lookup_rows, the
zero-copy reply path, and DeviceLedger lookup_async / lookup_finish, the
same reply left in flight): the single-chip DeviceLedger, the multi-chip
ShardedLedger, or the scalar OracleStateMachine — so VSR, the REPL, and the
client server all run unchanged on any of them, and wire-level parity tests
can diff backends byte-for-byte.
"""

from __future__ import annotations

import numpy as np

from tigerbeetle_tpu import types
from tigerbeetle_tpu.constants import ConfigCluster, DEFAULT_CLUSTER
from tigerbeetle_tpu.metrics import NULL_METRICS
from tigerbeetle_tpu.types import (
    ACCOUNT_DTYPE,
    CREATE_ACCOUNTS_RESULT_DTYPE,
    CREATE_TRANSFERS_RESULT_DTYPE,
    TRANSFER_DTYPE,
    Operation,
)

ID_SIZE = 16  # lookup request: packed little-endian u128 ids
EVENT_SIZE = 128
RESULT_SIZE = 8

_EVENT_DTYPES = {
    Operation.create_accounts: ACCOUNT_DTYPE,
    Operation.create_transfers: TRANSFER_DTYPE,
}
_LOOKUP_OPS = (Operation.lookup_accounts, Operation.lookup_transfers)
_RESULT_DTYPES = {
    Operation.create_accounts: CREATE_ACCOUNTS_RESULT_DTYPE,
    Operation.create_transfers: CREATE_TRANSFERS_RESULT_DTYPE,
}


def encode_results(sparse: list[tuple[int, int]], operation: Operation) -> bytes:
    """Sparse (index, result) pairs -> reply body bytes (reference:
    src/tigerbeetle.zig:231-249)."""
    out = np.zeros(len(sparse), dtype=_RESULT_DTYPES[operation])
    for i, (index, result) in enumerate(sparse):
        out[i]["index"] = index
        out[i]["result"] = result
    return out.tobytes()


def encode_sparse_results(codes: np.ndarray, operation: Operation) -> bytes:
    """Dense u32 codes -> sparse non-ok reply body, vectorized (reference:
    src/tigerbeetle.zig:231-249). Shared by the device and native
    backends' drain_reply."""
    idx = np.nonzero(codes)[0]
    out = np.zeros(len(idx), dtype=_RESULT_DTYPES[operation])
    out["index"] = idx.astype(np.uint32)
    out["result"] = codes[idx]
    return out.tobytes()


def decode_results(body: bytes, operation: Operation) -> list[tuple[int, int]]:
    assert len(body) % RESULT_SIZE == 0, len(body)
    arr = np.frombuffer(body, dtype=_RESULT_DTYPES[operation])
    return [(int(r["index"]), int(r["result"])) for r in arr]


def encode_ids(ids: list[int]) -> bytes:
    return types.ids_to_key4(ids).tobytes()


def decode_key4(body: bytes) -> np.ndarray:
    """A lookup body's ids as one read-only [n, 4] u32 view of its bytes
    (types.ids_to_key4's layout): no copy, no Python int."""
    assert len(body) % ID_SIZE == 0, len(body)
    return np.frombuffer(body, dtype="<u4").reshape(-1, 4)


def decode_ids(body: bytes) -> list[int]:
    return types.key4_to_ids(decode_key4(body))


def decode_accounts(body: bytes) -> np.ndarray:
    assert len(body) % EVENT_SIZE == 0, len(body)
    return np.frombuffer(body, dtype=ACCOUNT_DTYPE).copy()


def decode_transfers(body: bytes) -> np.ndarray:
    assert len(body) % EVENT_SIZE == 0, len(body)
    return np.frombuffer(body, dtype=TRANSFER_DTYPE).copy()


class StateMachine:
    """Drives a ledger backend with wire-format bodies.

    Lifecycle mirrors the reference (src/state_machine.zig:336-540):
      count = sm.input_count(op, body)   # body validation / batch sizing
      sm.prepare(op, count)              # advances prepare_timestamp
      reply = sm.commit(op, timestamp, body)
    """

    def __init__(self, backend, cluster: ConfigCluster = DEFAULT_CLUSTER,
                 metrics=NULL_METRICS):
        self.backend = backend
        self.cluster = cluster
        # how a lookup's ids reach the backend (metrics.CATALOG)
        self._c_keys_wire = metrics.counter("lookup.keys_wire")
        self._c_keys_scalar = metrics.counter("lookup.keys_scalar")

    # -- body validation & batch sizing --

    def batch_max(self, operation: Operation) -> int:
        """Per-op batch max = body_size_max / max(event_size, result_size)
        (reference: src/state_machine.zig:59-64 operation_batch_max) — the
        REPLY must fit in one message too, which is what bounds lookups
        (16-byte id events but 128-byte object results)."""
        body_max = self.cluster.message_size_max - 128  # header
        event = EVENT_SIZE if operation in _EVENT_DTYPES else ID_SIZE
        result = RESULT_SIZE if operation in _EVENT_DTYPES else EVENT_SIZE
        return body_max // max(event, result)

    def input_valid(self, operation: Operation, body: bytes) -> bool:
        if operation in _EVENT_DTYPES:
            event_size = EVENT_SIZE
        elif operation in _LOOKUP_OPS:
            event_size = ID_SIZE
        else:
            return False
        if len(body) == 0 or len(body) % event_size != 0:
            return False
        return len(body) // event_size <= self.batch_max(operation)

    def input_count(self, operation: Operation, body: bytes) -> int:
        assert self.input_valid(operation, body)
        size = (
            EVENT_SIZE
            if operation in _EVENT_DTYPES
            else ID_SIZE
        )
        return len(body) // size

    def prepare(self, operation: Operation, body: bytes) -> None:
        self.backend.prepare(operation, self.input_count(operation, body))

    @property
    def prepare_timestamp(self) -> int:
        return self.backend.prepare_timestamp

    @prepare_timestamp.setter
    def prepare_timestamp(self, value: int) -> None:
        self.backend.prepare_timestamp = value

    # -- commit: wire body in, wire reply out --

    def commit_async(self, operation: Operation, timestamp: int, body: bytes):
        """Dispatch a commit WITHOUT materializing results (the device
        launch is queued; results stay on device). Returns a handle for
        commit_finish: `(operation, pending)` when the backend left the
        work in flight, the reply bytes when it answered already. Creates
        are asynchronous on every backend with `execute_async` (device,
        sharded, native, dual; the oracle answers at once); a lookup is
        where the backend offers `lookup_async` and takes it (DeviceLedger
        alone: not a transfers lookup over a spill store) — it then reads
        the tables as they stand after every op dispatched before it, and
        its reply is built at commit_finish. Elsewhere (oracle, native,
        dual, sharded) a lookup computes its reply inline; on the sharded
        ledger that read is launched behind every create still in flight,
        one device stream, so it sees each of them.
        This is the replica's commit-stage overlap seam (reference:
        src/vsr/replica.zig:3045-3103 commit_dispatch stages)."""
        if operation in _LOOKUP_OPS:
            key4 = self._lookup_keys(operation, body)
            launch = getattr(self.backend, "lookup_async", None)
            pending = launch(operation, key4) if launch else None
            if pending is None:
                return self._lookup_reply(operation, key4)
            return (operation, pending)
        if operation not in _EVENT_DTYPES or not hasattr(
            self.backend, "execute_async"
        ):
            return self.commit(operation, timestamp, body)  # oracle
        if getattr(self.backend, "zero_copy_events", False):
            # backend only reads the rows: skip the 1 MiB defensive copy
            events = np.frombuffer(body, dtype=_EVENT_DTYPES[operation])
        else:
            events = (
                decode_accounts(body)
                if operation == Operation.create_accounts
                else decode_transfers(body)
            )
        return (operation, self.backend.execute_async(operation, timestamp, events))

    @staticmethod
    def handle_plan(handle):
        """The backend's wave-planner decision for a commit_async handle:
        (decision, wave_count) — e.g. ("waves", 3) — or None when the
        backend has no planner (oracle/native) or the op wasn't a create.
        The replica surfaces this as commit.group.wave_* without reaching
        into backend-specific pending types."""
        if isinstance(handle, bytes):
            return None
        return getattr(handle[1], "plan", None)

    def commit_group_async(self, operation: Operation, batches):
        """Fuse consecutive create_transfers commits into one device
        dispatch (group commit). `batches` = [(timestamp, body), ...].
        Returns a list of commit_async-compatible handles, or None when
        fusion is unavailable/unsound — callers fall back per batch."""
        if operation != Operation.create_transfers or len(batches) < 2:
            return None
        if not hasattr(self.backend, "try_execute_group_async"):
            return None
        # read-only views (no 1 MiB copy per batch): the group path only
        # reads the rows into the staging buffer
        items = [
            (ts, np.frombuffer(body, dtype=TRANSFER_DTYPE))
            for ts, body in batches
        ]
        pendings = self.backend.try_execute_group_async(items)
        if pendings is None:
            return None
        return [(operation, p) for p in pendings]

    def commit_finish_many(self, handles) -> None:
        """Pre-materialize several commit_async handles with one
        device->host transfer (see DeviceLedger.drain_many); the
        subsequent per-handle commit_finish calls hit the cache."""
        pendings = [
            h[1] for h in handles
            if not isinstance(h, bytes) and h[0] in _EVENT_DTYPES
        ]
        if pendings and hasattr(self.backend, "drain_many"):
            self.backend.drain_many(pendings)

    def commit_finish(self, handle) -> bytes:
        """Materialize a commit_async handle into the reply body bytes."""
        if isinstance(handle, bytes):
            return handle
        operation, pending = handle
        if operation in _LOOKUP_OPS:
            return self.backend.lookup_finish(pending)
        if hasattr(self.backend, "drain_reply"):
            # vectorized sparse encoding; empty for all-success without
            # materializing dense codes at all
            return self.backend.drain_reply(pending, operation)
        dense = self.backend.drain(pending)
        return encode_results(
            [(i, c) for i, c in enumerate(dense) if c], operation
        )

    def commit(self, operation: Operation, timestamp: int, body: bytes) -> bytes:
        if operation == Operation.create_accounts:
            events = decode_accounts(body)
            dense = self.backend.execute_dense(operation, timestamp, events)
            return encode_results(
                [(i, c) for i, c in enumerate(dense) if c], operation
            )
        if operation == Operation.create_transfers:
            events = decode_transfers(body)
            dense = self.backend.execute_dense(operation, timestamp, events)
            return encode_results(
                [(i, c) for i, c in enumerate(dense) if c], operation
            )
        if operation in _LOOKUP_OPS:
            return self._lookup_reply(
                operation, self._lookup_keys(operation, body)
            )
        raise AssertionError(operation)

    # -- lookups: the body's ids as one array --

    def _lookup_keys(self, operation: Operation, body: bytes) -> np.ndarray:
        """The lookup's ids as the body's own key4 view, booked by how they
        reach the backend: `lookup.keys_wire` as that array (lookup_rows /
        lookup_async), `lookup.keys_scalar` decoded to Python ints (the
        oracle; a backend whose `lookup_needs_ints` says so, a transfers
        lookup merged over a spill store)."""
        needs_ints = getattr(self.backend, "lookup_needs_ints", None)
        scalar = not hasattr(self.backend, "lookup_rows") or (
            needs_ints is not None and needs_ints(operation)
        )
        (self._c_keys_scalar if scalar else self._c_keys_wire).add()
        return decode_key4(body)

    def _lookup_reply(self, operation: Operation, key4: np.ndarray) -> bytes:
        """The reply body, computed now: found rows in request order,
        missing ids skipped."""
        if hasattr(self.backend, "lookup_rows"):  # every backend but the oracle
            return self.backend.lookup_rows(operation, key4)  # raw wire rows
        ids = types.key4_to_ids(key4)
        if operation == Operation.lookup_accounts:
            return types.accounts_to_np(self.backend.lookup_accounts(ids)).tobytes()
        return types.transfers_to_np(self.backend.lookup_transfers(ids)).tobytes()
