"""Collective operations' time during which no compute op runs on that
device, over the traced window; the worst device."""


def read(run):
    t = run["trace"]
    if not t or not any(d["collective_s"] > 0 for d in t["devices"].values()):
        return None
    worst = max(d["collective_exposed_s"] for d in t["devices"].values())
    return 100.0 * worst / t["window_s"]
