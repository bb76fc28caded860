"""What one ``XLA Ops`` event of this installation's device trace holds: a
small jitted program with a named scope, profiled, and every distinct field
of its device events printed. By hand, on the chip:

    python tools/trace_event_probe.py
"""
import os
import sys
import tempfile

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from sparkdl_tpu.models.olmo_hybrid import (
        gated_delta_chunked,
        gated_delta_step,
    )

    b, l, h, dk, dv = 1, 256, 30, 96, 192
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, l, h, dk))
    k = jax.random.normal(key, (b, l, h, dk)) / 10
    v = jax.random.normal(key, (b, l, h, dv))
    g = -jnp.abs(jax.random.normal(key, (b, l, h))) / 10
    beta = jax.nn.sigmoid(jax.random.normal(key, (b, l, h))) * 2
    s0 = jnp.zeros((b, h, dk, dv), jnp.float32)

    @jax.jit
    def both(q, k, v, g, beta, s0):
        o, s = gated_delta_chunked(q, k, v, g, beta, s0)
        o1, s = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], s)
        return o.sum() + o1.sum() + s.sum()

    jax.block_until_ready(both(q, k, v, g, beta, s0))
    out = tempfile.mkdtemp()
    jax.profiler.start_trace(out)
    for _ in range(3):
        jax.block_until_ready(both(q, k, v, g, beta, s0))
    jax.profiler.stop_trace()
    path = next(os.path.join(r, f) for r, _, fs in os.walk(out)
                for f in fs if f.endswith(".xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            print(f"line {line.name!r}: {len(events)} events")
            if line.name != "XLA Ops":
                continue
            total = sum(ev.duration_ns for ev in events)
            print(f"  XLA Ops total {total / 3e3:.1f} us a call")
            seen = set()
            for ev in events:
                head = ev.name.split(" = ")[0].rstrip("0123456789.")
                if head in seen:
                    continue
                seen.add(head)
                print("  name:", ev.name[:300])
                for key, val in ev.stats:
                    print(f"    stat {key!r}: {str(val)[:300]}")


if __name__ == "__main__":
    main()
