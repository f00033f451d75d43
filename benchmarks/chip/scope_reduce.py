"""Reduces a profiler trace (``.xplane.pb``) by the program's own names:
each device operation's time by the layer scope it was compiled under,
and each stretch of device idle time by the trainer phase the host was in.

- **Layer scopes.**  The program wraps each layer in ``jax.named_scope``
  (``SCOPES``).  A device operation's event is named by its HLO
  instruction's text with no metadata, so its scope is read from the
  compiled module's text (``compiled.as_text()``), where each instruction
  carries ``metadata={op_name="..."}``.  The join is by instruction name
  inside the module that ran it: each event on a device's ``XLA Modules``
  line is a module run, and the module text holding the most of that
  run's instruction names is taken as its text.  An operation is credited
  to the outermost of ``SCOPES`` in its ``op_name`` (``handoff`` wherever
  it stands, ``scope_of``); one whose module or instruction is not found,
  or whose ``op_name`` holds none of them, goes to ``other``.  Each
  stretch of device time goes to the innermost event running then, so a
  ``while`` is credited only where none of its body's operations has an
  event of its own, and the scopes plus ``other`` add up to the device's
  busy time.  Operations under a ``transpose(...)`` of automatic
  differentiation are the backward pass.
- **The texts** must be those of the programs that ran, compiled from the
  same source: JAX's persistent cache leaves metadata out of its key by
  default, so a cached program compiled from another commit's source runs
  under this one's name and its op names are that source's.
- **Host phases.**  ``Trainer.run`` writes each step's host work as the
  ``PHASES`` spans.  Device 0's idle time in the window (as
  ``trace_reduce`` defines both) is cut by the phase span around it; idle
  time under no phase span is ``outside``.  The longest gaps are named by
  the innermost span around their middle, phases included.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict

import trace_reduce as tr

SCOPES = ("embed", "encoder", "decoder", "handoff", "head", "optimizer")
PHASES = ("trainer.fetch", "trainer.put", "trainer.dispatch", "trainer.sync")
# opcodes of instructions that do work on the device, as an event of their own
WORK = re.compile(
    r"^(fusion|dot|convolution|while|conditional|call|custom-call|scatter|sort|"
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|collective-broadcast|ragged-all-to-all)"
    r"(-start)?$"
)
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$")  # a header: unindented, ends in "{"
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# computations an instruction runs as scheduled code, whose instructions are
# events of their own (a fusion's ``calls`` and a reduction's ``to_apply``
# are inlined into the one event)
RUNS = re.compile(r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
                  r"|\bbranch_computations=\{([^}]*)\}")
TRANSFORM = re.compile(r"^(?:jvp|transpose|vmap|remat|checkpoint)\((.*)\)$")


def scope_of(op_name: str) -> str | None:
    """The outermost of ``SCOPES`` in an ``op_name`` path, seen through
    transformation wrappers (``transpose(jvp(decoder))`` is ``decoder``),
    save that ``handoff`` wins wherever it stands: the wavefront's
    last-stage slice, inside the encoder's or decoder's scope, is where
    the compiler puts the phase boundary's reshard."""
    found = []
    for part in op_name.split("/"):
        while (m := TRANSFORM.match(part)):
            part = m.group(1)
        if part in SCOPES:
            found.append(part)
    return "handoff" if "handoff" in found else (found[0] if found else None)


def parse_module(text: str) -> dict:
    """``{instruction: (opcode, op_name, scope, computation)}`` of a compiled
    module's text, and under ``None`` the names of its scheduled
    computations (the entry, loop bodies and conditions, branches)."""
    out, comp, runs, entry = {}, None, set(), None
    for line in text.splitlines():
        c = COMPUTATION.match(line)
        if c:
            comp = c.group(2)
            if c.group(1):
                entry = comp
            continue
        m = INSTRUCTION.match(line)
        if not m:
            continue
        name, opcode = m.groups()
        on = OP_NAME.search(line)
        op = on.group(1) if on else ""
        out[name] = (opcode, op, scope_of(op), comp)
        for body, branches in RUNS.findall(line):
            runs.update([body] if body else [b.strip().lstrip("%") for b in branches.split(",")])
    out[None] = runs | {entry}
    return out


def work_without_scope(text: str) -> list[tuple[str, str, str]]:
    """``(instruction, opcode, op_name)`` of each instruction of a scheduled
    computation that does work and has no layer scope."""
    mod = parse_module(text)
    sched = mod.pop(None)
    return [(n, opc, op) for n, (opc, op, scope, comp) in mod.items()
            if comp in sched and scope is None and WORK.match(opc)]


def own_times(ops) -> list[float]:
    """Each interval's share of the union of ``ops`` (``(start, end, ...)``):
    every stretch of time goes to the latest-started interval running then."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own, stack, t = [0.0] * len(ops), [], None

    def credit(upto):
        nonlocal t
        while stack and ops[stack[-1]][1] <= upto:
            i = stack.pop()
            own[i] += max(0.0, ops[i][1] - t)
            t = max(t, ops[i][1])
        if stack and upto > t:
            own[stack[-1]] += upto - t
            t = upto

    for i in order:
        if t is not None:
            credit(ops[i][0])
        t = ops[i][0] if t is None else max(t, ops[i][0])
        stack.append(i)
    if stack:
        credit(float("inf"))
    return own


def _read(path):
    """Host spans ``(name, start, end)``, and per device its operations
    ``(start, end, event name)`` and module runs ``(start, end, name)``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
        elif tr.DEVICE_PLANE.match(plane.name):
            ops, runs = [], []
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events]
                    (ops if line.name == "XLA Ops" else runs).extend(evs)
            devices.append((plane.name, ops, sorted(runs)))
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    return spans, devices


def _window(spans, devices):
    steps = [(s, e) for name, s, e in spans if name == "train_step"]
    if steps:
        return min(s for s, _ in steps), max(e for _, e in steps), len(steps)
    every = [op for _, ops, _ in devices for op in ops]
    return min(s for s, _, _ in every), max(e for _, e, _ in every), 0


def _module_of(runs, modules, ops):
    """For each op, the parsed module of the module run around its start
    (the one holding most of that run's instruction names), or None."""
    starts = [s for s, _, _ in runs]
    names = defaultdict(list)
    where = []
    for s, _, n in ops:
        k = bisect.bisect_right(starts, s) - 1
        run = k if k >= 0 and s < runs[k][1] else None
        where.append(run)
        names[run].append(tr.op_name(n)[0])
    best = {}
    for run, inst in names.items():
        hits = [sum(i in m for i in inst) for m in modules]
        best[run] = modules[hits.index(max(hits))] if hits and max(hits) else None
    return [best[r] for r in where]


def reduce(path, texts: list[str]) -> dict:
    """Device seconds by layer scope and device 0's idle seconds by trainer
    phase, over the window ``trace_reduce.summarize`` reads; ``texts`` are
    the compiled modules that ran (``compiled.as_text()``)."""
    return reduce_events(*_read(path), texts)


def reduce_events(spans, devices, texts: list[str]) -> dict:
    """``reduce`` of a trace already read: host spans ``(name, start, end)``
    and per device ``(name, ops, module runs)``, as ``_read`` gives them."""
    lo, hi, steps = _window(spans, devices)
    modules = [parse_module(t) for t in texts]
    out_devices, gaps = [], []
    for i, (name, ops, runs) in enumerate(devices):
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops if e > lo and s < hi]
        own = own_times(inside)
        scopes = dict.fromkeys(SCOPES, 0.0)
        other = fwd = bwd = 0.0
        for (s, e, n), t, mod in zip(inside, own, _module_of(runs, modules, inside)):
            info = mod.get(tr.op_name(n)[0]) if mod else None
            scope = info[2] if info else None
            t /= 1e9
            if scope is None:
                other += t
                continue
            scopes[scope] += t
            if scope != "optimizer":
                if "transpose(" in info[1]:
                    bwd += t
                else:
                    fwd += t
        out_devices.append({"name": name, "scopes_s": scopes, "other_s": other, "forward_s": fwd,
                            "backward_s": bwd, "busy_s": sum(scopes.values()) + other})
        if i == 0:
            gaps = tr.subtract([(lo, hi)], tr.union((s, e) for s, e, _ in inside))
    out = {"window_s": (hi - lo) / 1e9, "steps": steps, "devices": out_devices, "idle_s": {}, "idle_gaps": []}
    if devices:
        out["idle_s"], out["idle_gaps"] = _idle_by_phase(spans, gaps, lo, hi)
    return out


def _idle_by_phase(spans, gaps, lo, hi):
    """Seconds of ``gaps`` inside each phase's spans and under none, and the
    longest gaps, each named by the innermost span around its middle."""
    idle = dict.fromkeys(PHASES, 0.0)
    for n, s, e in spans:
        if n in PHASES and e > lo and s < hi:
            span = [(max(s, lo), min(e, hi))]
            idle[n] += tr.length(tr.subtract(span, tr.subtract(span, gaps))) / 1e9
    idle["outside"] = tr.length(gaps) / 1e9 - sum(idle.values())
    named = [sp for sp in spans if sp[0] in tr.HOST_SPANS or sp[0] in PHASES]
    longest = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:tr.TOP]:
        mid = (s + e) / 2
        around = [(ee - ss, label) for label, ss, ee in named if ss <= mid <= ee]
        longest.append([min(around)[1] if around else "host", (e - s) / 1e9])
    return idle, longest


def metrics(reduction: dict, tgt_tokens: float) -> dict:
    """The per-layer numbers of a reduction: ``device_us_per_tok.<scope>``,
    a scope's device seconds averaged over the devices per non-pad target
    token of the traced steps, in microseconds; ``idle_ms_per_step.<phase>``,
    device 0's idle time in that phase per traced step, in milliseconds."""
    devs = reduction["devices"]
    out = {}
    if devs and tgt_tokens:
        for scope in ("encoder", "decoder", "head", "optimizer"):
            out[f"device_us_per_tok.{scope}"] = 1e6 * sum(d["scopes_s"][scope] for d in devs) / len(devs) / tgt_tokens
    if reduction["steps"] and reduction["idle_s"]:
        for phase in PHASES:
            out[f"idle_ms_per_step.{phase.split('.', 1)[1]}"] = 1e3 * reduction["idle_s"][phase] / reduction["steps"]
    return out
