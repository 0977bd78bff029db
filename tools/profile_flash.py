"""Per-kernel device-trace timing for the flash attention kernels.

Wall time around a kernel includes dispatch and whatever else the step
runs; jax.profiler device traces record the on-chip kernel durations
directly. This tool runs fwd / bwd at given block
sizes under a trace and reports the summed duration of each pallas
kernel's events on the TPU plane.
"""
from __future__ import annotations

import glob
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np


def device_kernel_times(fn, *args, warmup: int = 2, iters: int = 6):
    """Run fn(*args) under a profiler trace; return {kernel_name:
    total_duration_ms / iters} for TPU-plane events, plus the total device
    time per iter."""
    from jax.profiler import ProfileData

    def fence(out):
        leaf = jax.tree.leaves(out)[0]
        np.asarray(leaf.reshape(-1)[0] if leaf.ndim else leaf)

    for _ in range(warmup):
        out = fn(*args)
    fence(out)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(iters):
            out = fn(*args)
        fence(out)
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        assert paths, "no xplane written"
        data = ProfileData.from_file(paths[0])
        totals: dict[str, float] = {}
        for plane in data.planes:
            if "TPU" not in plane.name and "tpu" not in plane.name:
                continue
            for line in plane.lines:
                for ev in line.events:
                    totals[ev.name] = (
                        totals.get(ev.name, 0.0) + ev.duration_ns / 1e6
                    )
    return {k: v / iters for k, v in sorted(
        totals.items(), key=lambda kv: -kv[1]
    )}


def main():
    from tony_tpu.ops import flash_attention

    seq = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    heads, d = 16, 64
    rng = np.random.default_rng(0)
    shape = (batch, seq, heads, d)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
               for _ in range(3))

    fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    loss = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32)
        ), argnums=(0, 1, 2),
    ))
    print(f"== fwd seq={seq} batch={batch} ==")
    for name, ms in list(device_kernel_times(fwd, q, k, v).items())[:8]:
        print(f"  {ms:9.3f} ms  {name}")
    print(f"== fwd+bwd ==")
    for name, ms in list(device_kernel_times(loss, q, k, v).items())[:12]:
        print(f"  {ms:9.3f} ms  {name}")


if __name__ == "__main__":
    main()
