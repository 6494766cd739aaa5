"""The load generator: TCP sessions of the program's own client
(vsr/client.py over io/message_bus.py, the wire a user's client speaks)
driven from this one thread. Copies (PR 25) of benchmark.run_frontier's
open-loop scheduling and due-time stamping and of chip_smoke.drive's
closed loop. A request the replica drops and the client re-sends on its
timeout ladder is ONE request here; its re-sends are counted apart.
"""

from __future__ import annotations

import dataclasses
import random
import time

from benchmarks.reference.wire_types import Operation

CREATE = int(Operation.create_transfers)


@dataclasses.dataclass
class Record:
    operation: int
    body: bytes
    cls: str  # batch class, or "lookup_accounts" / "lookup_transfers" / "setup"
    phase: str  # load | warm | window | after
    due: float = 0.0
    sent: float = 0.0
    done: float = 0.0  # 0.0 = never answered
    op: int = 0
    ts: int = 0
    reply: bytes | None = None
    resends: int = 0
    error: str | None = None
    events: int = 0


class _Counters:
    """The least registry vsr.client.Client needs, to count re-sends."""

    class _C:
        __slots__ = ("value",)

        def __init__(self):
            self.value = 0

        def add(self, v=1):
            self.value += v

    def __init__(self):
        self._c: dict = {}

    def counter(self, name):
        return self._c.setdefault(name, self._C())

    def value(self, name) -> int:
        c = self._c.get(name)
        return c.value if c else 0


class Session:
    def __init__(self, index: int, client_id: int, port: int, params: dict,
                 seed: int):
        from tigerbeetle_tpu.io.message_bus import TCPMessageBus
        from tigerbeetle_tpu.vsr.client import Client, WallTicker

        self.index = index
        self.bus = TCPMessageBus([("127.0.0.1", port)], client_id)
        self.counters = _Counters()
        self.client = Client(
            client_id, self.bus, replica_count=1,
            request_timeout_ticks=int(params["request_timeout_ticks"]),
            ping_ticks=int(params["ping_ticks"]),
            max_backoff_exponent=int(params["max_backoff_exponent"]),
            metrics=self.counters,
        )
        # one rng feeds the timeout ladder's jitter and the busy ladder:
        # seeded from --seed and the session, not from the client id
        self.client.rng.seed(random.Random(f"{seed}/{index}").getrandbits(64))
        self.ticker = WallTicker(self.client, tick_s=float(params["tick_s"]))
        self.record: Record | None = None
        self._resends0 = 0

    def register(self, deadline_s: float = 120.0) -> None:
        self.client.register()
        t0 = time.monotonic()
        while not self.client.done:
            self.bus.pump(timeout=0.001)
            now = time.monotonic()
            self.ticker.advance(now)
            if now - t0 > deadline_s:
                raise TimeoutError("session registration got no reply")
        self.client.take_reply()

    def send(self, rec: Record, now: float) -> None:
        assert self.record is None
        rec.sent = now
        self._resends0 = self.counters.value("client.resends")
        self.client.request(Operation(rec.operation), rec.body)
        self.record = rec

    def poll(self, now: float) -> Record | None:
        """Pump the wire; the finished record, once its reply is in."""
        self.bus.pump(timeout=0.0)
        rec = self.record
        if rec is None:
            return None
        if not self.client.done:
            self.ticker.advance(now)
            return None
        rec.resends = self.counters.value("client.resends") - self._resends0
        self.record = None
        try:
            header, reply = self.client.take_reply()
        except Exception as e:  # typed client error: the request failed
            rec.error = f"{type(e).__name__}: {e}"
            return rec
        rec.done = time.monotonic()
        rec.op, rec.ts, rec.reply = header.op, header.timestamp, reply
        return rec

    def close(self) -> None:
        self.bus.drop_connections()


class Load:
    def __init__(self, port: int, n_sessions: int, params: dict, seed: int):
        self.sessions = [
            Session(i, 0xBE0000 + i, port, params, seed)
            for i in range(n_sessions)
        ]
        for s in self.sessions:
            s.register()
        self.records: list[Record] = []

    def close(self) -> None:
        for s in self.sessions:
            s.close()

    def call(self, rec: Record, deadline_s: float = 600.0) -> Record:
        """One request on session 0, waited for (set-up and read-back)."""
        s = self.sessions[0]
        now = time.monotonic()
        rec.due = now
        s.send(rec, now)
        while True:
            now = time.monotonic()
            done = s.poll(now)
            if done is not None:
                self.records.append(done)
                return done
            if now - rec.sent > deadline_s:
                rec.error = "no reply"
                s.record = None
                self.records.append(rec)
                return rec
            time.sleep(0.0002)

    def drain(self, deadline_s: float) -> None:
        """Wait for every request in flight (an answer that comes late is
        late, not wrong); what never comes stays done == 0. The window has
        closed, so a session that sleeps on its timeout ladder (up to 48 s
        and more) is woken twice a second: nothing measured sees it."""
        t0 = time.monotonic()
        next_nudge = t0 + 0.5
        while any(s.record is not None for s in self.sessions):
            now = time.monotonic()
            progressed = False
            if now >= next_nudge:
                next_nudge = now + 0.5
                for s in self.sessions:
                    if s.record is not None and s.client.in_flight is not None:
                        s.client.resend()
            for s in self.sessions:
                rec = s.poll(now)
                if rec is not None:
                    self.records.append(rec)
                    progressed = True
            if now - t0 > deadline_s:
                for s in self.sessions:
                    if s.record is not None:
                        s.record.error = "no reply"
                        self.records.append(s.record)
                        s.record = None
                return
            if not progressed:
                time.sleep(0.0002)


def create_record(stream, phase: str) -> Record:
    cls, arr = stream.next_create()
    return Record(CREATE, arr.tobytes(), cls, phase, events=len(arr))


def lookup_record(stream, phase: str) -> Record:
    op, ids = stream.next_lookup()
    cls = "lookup_accounts" if op == int(Operation.lookup_accounts) else "lookup_transfers"
    return Record(op, ids.tobytes(), cls, phase, events=len(ids) // 2)


def drive_closed(load: Load, stream, warm_requests: int, seconds: float,
                 plateau, on_window_start, on_window_end) -> dict:
    """Closed loop: every session keeps one create request in flight, from
    warm-up straight into the window (so the follower's lag is at its
    plateau when the clock starts) and until the window closes. The window
    opens once `warm_requests` are acknowledged and `plateau()` holds."""
    sessions = load.sessions
    now = time.monotonic()
    for s in sessions:
        s.send(create_record(stream, "warm"), now)
    acked = 0
    phase = "warm"
    t0 = t_end = 0.0
    next_check = 0.0
    warm_deadline = time.monotonic() + 900.0
    while True:
        now = time.monotonic()
        if phase == "warm" and acked >= warm_requests and now >= next_check:
            next_check = now + 0.25
            if plateau():
                on_window_start()
                t0 = time.monotonic()
                t_end = t0 + seconds
                phase = "window"
                now = t0
        if phase == "warm" and now > warm_deadline:
            raise TimeoutError("warm-up never reached its plateau")
        if phase == "window" and now >= t_end:
            break
        progressed = False
        for s in sessions:
            rec = s.poll(now)
            if rec is None:
                continue
            progressed = True
            load.records.append(rec)
            if rec.error is None:
                acked += 1
            s.send(create_record(stream, phase), time.monotonic())
        if not progressed:
            time.sleep(0.0002)
    on_window_end()
    t_closed = time.monotonic()
    load.drain(deadline_s=120.0)
    return {"t0": t0, "t_end": t_end, "drain_s": time.monotonic() - t_closed}


def drive_open(load: Load, stream, rate: float, warm_ticks: int,
               seconds: float, settle, on_window_start,
               on_window_end) -> dict:
    """Open loop at `rate` ticks a second; each tick is one create request
    and (where the mix reads) one lookup, each stamped with its DUE time,
    so a stall counts against every request it delays. The schedule is a
    pure function of (file, seed), so every record of a phase is built
    BEFORE its clock starts: inside the phase this thread only sends what
    is due and polls the sessions. Warm-up is `warm_ticks` ticks of the
    same schedule, drained, then `settle()`."""
    with_lookups = bool(stream.mix.get("lookups"))

    def build(phase: str, ticks: int) -> list[list[Record]]:
        out = []
        for _ in range(ticks):
            tick = [create_record(stream, phase)]
            if with_lookups:
                tick.append(lookup_record(stream, phase))
            out.append(tick)
        return out

    most_in_flight = 0

    def run(planned: list[list[Record]]) -> tuple[float, float]:
        nonlocal most_in_flight
        ticks = len(planned)
        interval = 1.0 / rate
        t0 = time.monotonic() + 0.05
        backlog: list[Record] = []
        idle = [s for s in load.sessions if s.record is None]
        k = 0
        while k < ticks or backlog or len(idle) < len(load.sessions):
            now = time.monotonic()
            while k < ticks and t0 + k * interval <= now:
                for rec in planned[k]:
                    rec.due = t0 + k * interval
                    backlog.append(rec)
                k += 1
            while backlog and idle:
                idle.pop().send(backlog.pop(0), time.monotonic())
            most_in_flight = max(most_in_flight, len(load.sessions) - len(idle))
            progressed = False
            for s in load.sessions:
                rec = s.poll(now)
                if rec is not None:
                    load.records.append(rec)
                    idle.append(s)
                    progressed = True
            if k >= ticks and now > t0 + ticks * interval + 120.0:
                break  # what is still out never came
            if not progressed:
                time.sleep(0.0002)
        return t0, t0 + ticks * interval

    run(build("warm", warm_ticks))
    window = build("window", int(round(rate * seconds)))
    settle()
    most_in_flight = 0
    on_window_start()
    t0, t_end = run(window)
    on_window_end()
    load.drain(deadline_s=5.0)
    return {"t0": t0, "t_end": t_end, "drain_s": 0.0,
            "most_in_flight": most_in_flight}
