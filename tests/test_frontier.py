"""Load/latency frontier sweep (benchmark.run_frontier) — tier-1 smoke.

A tiny two-step ladder against a real spawned server (native backend,
CPU-pinned): every step must carry offered/achieved tps, p50/p95/p99,
the typed-shed rate, and a dominant-leg attribution sourced from the
server's per-request latency anatomy over the wire — and the slowest
sampled request's breakdown must ACCOUNT for its end-to-end latency
(legs are consecutive stamp intervals; the acceptance bound is 20%).
The full ladder (`--backend dual`, 4+ steps) is scripts/frontier.py.
"""

import tests.conftest  # noqa: F401 — CPU platform before jax init
from tigerbeetle_tpu.latency import LEGS


def test_frontier_smoke_two_steps():
    from tigerbeetle_tpu.benchmark import run_frontier

    out = run_frontier(
        steps=(2_000, 6_000),
        step_s=1.5,
        batch=256,
        sessions=8,
        conns=2,
        n_accounts=64,
        backend="native",
        jax_platform="cpu",
    )
    steps = out["steps"]
    assert len(steps) == 2
    for s in steps:
        assert s["offered_tps"] in (2_000, 6_000)
        assert s["achieved_tps"] > 0
        assert s["acked_events_in_window"] > 0
        assert s["p50_ms"] > 0 and s["p99_ms"] >= s["p50_ms"]
        assert s["failures"] == 0
        assert 0.0 <= s["shed_rate"] <= 1.0
        assert s["dominant_leg"] in LEGS
        assert 0.0 < s["dominant_leg_share"] <= 1.0
    assert out["peak_achieved_tps"] >= steps[0]["achieved_tps"]
    # the decomposition accounts for the slowest request's time: legs
    # are consecutive intervals, so their sum must be within 20% of the
    # measured e2e (in practice it is exact minus rounding)
    b = out["breakdown"]
    assert b is not None, "no sampled breakdown from the live server"
    assert b["e2e_us"] > 0
    assert abs(b["accounted_ratio"] - 1.0) <= 0.2, b
    assert b["dominant"] in b["legs"]
    assert set(b["legs"]) <= set(LEGS)
