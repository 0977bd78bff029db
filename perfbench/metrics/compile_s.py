"""Sum of the program's tony_compile_ms over the programs the cell warms
(first call of each: trace, compile or cache load, one execution)."""


def read(run):
    return run["job"].get("compile_s") or None
