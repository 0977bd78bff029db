"""Per-direction flash block sweep, timed by DEVICE-TRACE kernel
durations. Sweeps (block_q, block_k)
independently for the fwd kernel and the two backward kernels and prints
a table; ops/attention.py `_default_blocks` records the chosen defaults.

A WALL-clock cross-check closes the sweep (fwd+bwd through the public
`flash_attention`, many iterations so dispatch amortizes): a
kernel-only sweep once pinned 1024 everywhere while the 2k wall time
regressed by half — per-kernel durations miss inter-kernel pipelining,
so a pin needs both tables to agree.
Needs a real TPU: Pallas on the CPU backend is interpret-only."""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, "/root/repo")
from tools.profile_flash import device_kernel_times  # noqa: E402

from tony_tpu.ops.attention import (  # noqa: E402
    _flash_attention_pallas,
    _flash_attention_pallas_bwd,
)


def main():
    seq = int(sys.argv[1]) if len(sys.argv) > 1 else 8192
    bh = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    d = int(sys.argv[3]) if len(sys.argv) > 3 else 64
    rng = np.random.default_rng(0)
    q, k, v, do = (
        jnp.asarray(rng.normal(size=(bh, seq, d)), jnp.bfloat16)
        for _ in range(4)
    )
    scale = d ** -0.5

    fwd_ref = jax.jit(lambda q, k, v: _flash_attention_pallas(  # tony: noqa[TONY-X001] — sweep tool: one reference compile per run
        q, k, v, causal=True, scale=scale, block_q=512, block_k=512,
        return_lse=True,
    ))
    out, lse = fwd_ref(q, k, v)  # tony: noqa[TONY-X001] — reference output computed once per sweep run

    blocks = [256, 512, 1024, 2048]
    print(f"== fwd, seq={seq} (kernel ms) ==")
    for bq in blocks:
        for bk in blocks:
            try:
                fn = jax.jit(lambda q, k, v, bq=bq, bk=bk:  # tony: noqa[TONY-X001] — sweep point: one compile per block config is the tool's job
                             _flash_attention_pallas(
                                 q, k, v, causal=True, scale=scale,
                                 block_q=bq, block_k=bk))
                times = device_kernel_times(fn, q, k, v, warmup=1, iters=4)
                kern = sum(ms for n, ms in times.items()
                           if "custom-call" in n)
                print(f"  bq={bq:5d} bk={bk:5d}  {kern:7.3f}")
            except Exception as e:
                print(f"  bq={bq:5d} bk={bk:5d}  FAIL "
                      f"{str(e).splitlines()[0][:70]}")

    print(f"== bwd (dq + dkv kernel ms; dq=single-out, dkv=tuple-out) ==")
    for bq in blocks:
        for bk in blocks:
            try:
                fn = jax.jit(lambda q, k, v, out, lse, do, bq=bq, bk=bk:  # tony: noqa[TONY-X001] — sweep point: one compile per block config is the tool's job
                             _flash_attention_pallas_bwd(
                                 q, k, v, out, lse, do, causal=True,
                                 scale=scale, block_q=bq, block_k=bk))
                times = device_kernel_times(fn, q, k, v, out, lse, do,
                                            warmup=1, iters=4)
                dq_ms = sum(
                    ms for n, ms in times.items()
                    if "custom-call" in n and not n.startswith("%")
                    or ("custom-call" in n and " = bf16" in n)
                )
                # attribute by output arity: dkv returns a tuple
                dkv_ms = sum(ms for n, ms in times.items()
                             if "custom-call" in n and " = (bf16" in n)
                dq_ms = sum(ms for n, ms in times.items()
                            if "custom-call" in n) - dkv_ms
                print(f"  bq={bq:5d} bk={bk:5d}  dq={dq_ms:7.3f}  "
                      f"dkv={dkv_ms:7.3f}")
            except Exception as e:
                print(f"  bq={bq:5d} bk={bk:5d}  FAIL "
                      f"{str(e).splitlines()[0][:70]}")

    # Wall cross-check: now the autotuner's reusable block-size stage
    # (parallel/autotune.py `tune_flash_blocks` — the same grad-of-sum
    # fwd+bwd measurement this tool used to inline). force=True: a
    # sweep tool exists to re-measure, so the persisted record never
    # short-circuits it; the fresh result is persisted for consumers.
    from tony_tpu.parallel import autotune

    print(f"== wall fwd+bwd, seq={seq} (ms/iter, best of "
          f"{3} windows; autotune stage) ==")
    rec = autotune.tune_flash_blocks(
        seq, bh, d, blocks=blocks, force=True,
        trial_budget=len(blocks) * len(blocks) + 1,
    )
    for trial in rec.get("trials", []):
        knobs = trial.get("knobs") or {}
        bq = knobs.get("block_q") or "dflt"
        bk = knobs.get("block_k") or "dflt"
        if "error" in trial:
            print(f"  bq={bq!s:>5s} bk={bk!s:>5s}  FAIL "
                  f"{str(trial['error'])[:70]}")
        else:
            print(f"  bq={bq!s:>5s} bk={bk!s:>5s}  {trial['ms']:7.3f}")
    best = rec.get("best") or {}
    print(f"  winner: bq={best.get('block_q')} bk={best.get('block_k')} "
          f"{rec.get('best_ms')} ms (default {rec.get('default_ms')} ms; "
          f"record persisted under key {str(rec.get('key'))[:16]}…)")


if __name__ == "__main__":
    main()
