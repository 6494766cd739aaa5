"""Device probe: what a device->host fetch does to this process's dispatch.

The native-serves-replies design (`--backend dual`) rests on a measurement
made on an earlier rig: after a process's FIRST device->host fetch every
later kernel launch and host->device upload was permanently slower. This
script re-measures exactly that on whatever device JAX gives it, in a
process of its own (the chip belongs to one process at a time — run it
when no server holds the chip):

- dispatch time of a trivial jitted call, chained (200 launches blocked
  once at the end, per launch) and synchronous (launch + block, each),
  medians, BEFORE and AFTER the first fetch;
- host->device rate of a 64 MiB upload, median of 5, before and after;
- the fetch itself (64 MiB device->host rate).

Prints ONE JSON line. Like every entry point that touches a device, it
names the device and refuses a CPU that nobody asked for by name.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MIB = 1 << 20
UPLOAD_BYTES = 64 * MIB


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import tigerbeetle_tpu  # noqa: F401  (x64 + the compile-cache rule)
    from tigerbeetle_tpu.cli import announce_device

    plat = os.environ.get("TB_JAX_PLATFORM")
    if plat:
        jax.config.update("jax_platforms", plat)
    device = announce_device()

    step = jax.jit(lambda x: x + jnp.uint32(1))
    x0 = jnp.zeros(8, dtype=jnp.uint32)
    jax.block_until_ready(step(x0))  # absorb the compile
    host = np.random.default_rng(0).integers(
        0, 256, size=UPLOAD_BYTES, dtype=np.uint8
    )

    def measure() -> dict:
        chained = []
        for _ in range(9):
            x = x0
            t0 = time.perf_counter()
            for _ in range(200):
                x = step(x)
            jax.block_until_ready(x)
            chained.append((time.perf_counter() - t0) / 200 * 1e6)
        sync = []
        for _ in range(200):
            t0 = time.perf_counter()
            jax.block_until_ready(step(x0))
            sync.append((time.perf_counter() - t0) * 1e6)
        h2d = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(jax.device_put(host))
            h2d.append(UPLOAD_BYTES / MIB / (time.perf_counter() - t0))
        return {
            "dispatch_us_chained": statistics.median(chained),
            "dispatch_us_sync": statistics.median(sync),
            "h2d_mib_s": statistics.median(h2d),
        }

    before = measure()
    big = jax.block_until_ready(jax.device_put(host))
    t0 = time.perf_counter()
    back = np.asarray(big)  # the process's FIRST device->host fetch
    d2h = UPLOAD_BYTES / MIB / (time.perf_counter() - t0)
    assert back[:16].tobytes() == host[:16].tobytes()
    after = measure()
    print(json.dumps({
        "probe": "device",
        "device": device,
        "before_first_fetch": before,
        "after_first_fetch": after,
        "d2h_mib_s_first_fetch": d2h,
        "upload_bytes": UPLOAD_BYTES,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
