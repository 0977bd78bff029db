"""Asserts per-slice identity when slices span MULTIPLE hosts — the
placement path one host per slice leaves untested: with hosts_per_slice>1,
task index i must land on slice i // hosts as in-slice process i % hosts.
Run with 4 workers x tpus=4 pinned to v4-16 => 2 slices of 2 hosts each."""
import os
import sys

import tony_tpu.runtime as rt

ctx = rt.task_context()
plan = rt.slice_topology()
if plan is None or plan["hosts_per_slice"] != 2 or plan["num_slices"] != 2:
    print(f"expected 2 slices x 2 hosts, got {plan}", file=sys.stderr)
    sys.exit(2)
want_slice, want_proc = divmod(ctx.task_index, 2)
if ctx.slice_index != want_slice or ctx.slice_process_id != want_proc:
    print(f"slice identity wrong: task {ctx.task_index} -> "
          f"slice {ctx.slice_index}/{ctx.slice_process_id}, want "
          f"{want_slice}/{want_proc}", file=sys.stderr)
    sys.exit(3)
if os.environ.get("MEGASCALE_SLICE_ID") != str(want_slice):
    print(f"MEGASCALE_SLICE_ID = "
          f"{os.environ.get('MEGASCALE_SLICE_ID')!r}, want {want_slice}",
          file=sys.stderr)
    sys.exit(4)
# One flat jax.distributed identity across both slices.
if ctx.num_processes != 4:
    print(f"num_processes = {ctx.num_processes}", file=sys.stderr)
    sys.exit(5)
sys.exit(0)
