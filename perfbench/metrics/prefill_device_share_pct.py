"""Device time inside the prefill_chunks program over device busy time."""
from yardstick import xplane


def read(run):
    t = run["trace"]
    prog = xplane.program(t, "prefill_chunks") if t else None
    if not prog or t["busy_s"] <= 0:
        return None
    return 100.0 * prog["total_s"] / len(t["devices"]) / t["busy_s"]
