"""Share of the traced window in which no operation ran on a device, in %,
averaged over the cell's devices (profiler trace)."""


def read(run: dict):
    t = run.get("trace")
    if not t or not t["devices"]:
        return None
    return 100.0 * sum(1.0 - d["busy_s"] / t["window_s"] for d in t["devices"]) / len(t["devices"])
