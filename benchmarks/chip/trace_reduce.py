"""Reduces a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the numbers the per-layer metrics read.

- The window is the host's ``train_step`` spans, first start to last end
  (the device's operations, first to last, where the trace has no span).
- A device is a plane named ``/device:<KIND>:<n>``; its operations are
  the events of its ``XLA Ops`` line.
- ``busy_s``: the union of a device's operation intervals in the window.
- ``collective_s`` / ``exposed_collective_s``: the union of its collective
  operations' intervals (classified by HLO opcode, the name's prefix), and
  the part of that union in which no other operation ran on the device.
- ``top_ops``: operation names by total device time, averaged over the
  devices.
- ``idle_gaps``: the longest spans of the window in which device 0 ran
  nothing, each named by the innermost host span around its middle
  (``batch_fetch``, ``train_step``, ``compiling``), or ``host`` where no
  span covers it.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
# an operation's event is named by its HLO text,
# "%name = <shape> opcode(operands), attributes", or by its name alone
HLO = re.compile(r"^%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|collective-broadcast|ragged-all-to-all)"
    r"(-start|-done)?$"
)
HOST_SPANS = ("train_step", "batch_fetch")
TOP = 10


def op_name(event_name: str) -> tuple[str, str]:
    """``(instruction, opcode)`` of a device operation's event."""
    m = HLO.match(event_name)
    if m:
        return m.group(1), m.group(2)
    name = event_name.lstrip("%").split(" ", 1)[0]
    return name, re.sub(r"\.\d+$", "", name)


def is_collective(event_name: str) -> bool:
    return bool(COLLECTIVE.match(op_name(event_name)[1]))


def union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def find_xplane(log_dir) -> Path:
    found = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(found[-1])


def summarize(path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name in HOST_SPANS or "ompile" in name:
                        label = name if name in HOST_SPANS else "compiling"
                        spans.append((label, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events]
            devices.append((plane.name, ops))
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    steps = [(s, e) for label, s, e in spans if label == "train_step"]
    if steps:
        lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    else:
        every = [op for _, ops in devices for op in ops]
        lo, hi = min(s for s, _, _ in every), max(e for _, e, _ in every)
    window_ns = hi - lo
    out_devices, totals = [], {}
    gaps = []
    for i, (name, ops) in enumerate(devices):
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops if e > lo and s < hi]
        busy = union((s, e) for s, e, _ in inside)
        coll = union((s, e) for s, e, n in inside if is_collective(n))
        other = union((s, e) for s, e, n in inside if not is_collective(n))
        out_devices.append({
            "name": name,
            "busy_s": length(busy) / 1e9,
            "collective_s": length(coll) / 1e9,
            "exposed_collective_s": length(subtract(coll, other)) / 1e9,
        })
        for s, e, n in inside:
            instr, opcode = op_name(n)
            key = instr if instr.startswith(opcode) else f"{instr} ({opcode})"
            totals[key] = totals.get(key, 0.0) + (e - s) / 1e9
        if i == 0:
            gaps = subtract([(lo, hi)], busy)
    n_dev = max(len(devices), 1)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    idle = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = (s + e) / 2
        around = [(ee - ss, label) for label, ss, ee in spans if ss <= mid <= ee]
        idle.append([min(around)[1] if around else "host", (e - s) / 1e9])
    return {
        "window_s": window_ns / 1e9,
        "devices": out_devices,
        "top_ops": [[n, t / n_dev] for n, t in top],
        "idle_gaps": idle,
    }
