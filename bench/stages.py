"""What the program records about its own calls, read for the per-layer
metrics of the entry, scan and slot-step layers.

Host spans: ``repro.core.telemetry.host_spans()`` holds the program's
spans of its last calls. A ``simulate`` call is one ``sim.simulate``
span (counter ``slots``) over ``sim.prepare``, ``sim.init_state``,
``sim.dispatch``, ``sim.scan_wait``, ``sim.fetch`` and ``sim.finalize``;
a ``run_sweep`` call is one ``sweep.run`` over ``sweep.prepare`` and, per
group, ``sweep.stack``, ``sweep.dispatch``, ``sweep.scan_wait``,
``sweep.fetch`` and ``sweep.stats``. ``window_calls`` takes the calls of
the window back from the newest: nothing the harness does after the
window calls the program.

Stage scopes: ``sim.step_fn`` runs each stage under a ``jax.named_scope``
and the sweep's histogram fold under ``stream_fold``, so the ops of the
scan carry a scope in their ``metadata.op_name``. A TPU op event names
only its HLO instruction, so ``hlo_scopes`` looks the scope up in the
scan's HLO module, which the trace keeps in its ``/host:metadata``
plane; ``attribute`` counts each op's self time (its duration less the
part of it that nested ops on the same line cover) under that scope.
``stage_shares`` parses the window's trace once (memoised per file),
reading only the device planes and, on each device, only the ops of the
first execution of the scan module: a whole call, whatever the trace
left out later. The shares come from those slots alone, so they are
multiplied by the span-timed scan to give microseconds per slot.

Where the program records none of this (an older program has no spans
and no scopes), or the trace has no device plane, the functions return
``None``.
"""
from __future__ import annotations

import glob
import os
from pathlib import Path

SCAN_MODULES = {"simulate": "jit__run", "run_sweep": "jit__sweep_batch"}
ROOT_SPANS = {"simulate": "sim.simulate", "run_sweep": "sweep.run"}
SCAN_PARTS = ("dispatch", "scan_wait")
FINALIZE_PARTS = {"simulate": ("fetch", "finalize"),
                  "run_sweep": ("fetch", "stats")}
SCOPES = ("fused_precompute", "grants", "sender_select", "route",
          "uplink_drain", "downlink_drain", "stats", "recovery",
          "post_step", "telemetry", "stream_fold")
UNSCOPED = "unscoped"
NESTING = {"while", "conditional", "call"}   # opcodes that run nested ops
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

_PARSED: dict = {}


def window_calls(record: dict) -> list[dict] | None:
    """The window's calls of the entry, oldest first, each with its
    ``slots`` and its children's host nanoseconds by stage name
    (``dispatch``, ``scan_wait``, ...). ``None`` when the record of
    spans does not hold the window's run-slots exactly."""
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    host_spans = getattr(telemetry, "host_spans", None)
    want = record.get("runs", 0) * record.get("slots_per_run", 0)
    root = ROOT_SPANS.get(record.get("entry"))
    if host_spans is None or want <= 0 or root is None:
        return None
    spans = host_spans()
    children: dict = {}
    for s in spans:
        if s["parent"] == root:
            part = s["name"].split(".", 1)[1]
            ns = children.setdefault(s["call"], {})
            ns[part] = ns.get(part, 0) + s["end_ns"] - s["start_ns"]
    calls, got = [], 0
    for s in reversed(spans):
        if got >= want:
            break
        if s["name"] == root and s["parent"] is None:
            slots = int(s["counts"].get("slots", 0))
            calls.append({"slots": slots,
                          "ns": children.get(s["call"], {})})
            got += slots
    return calls[::-1] if got == want else None


def scan_wall_us_per_slot(record: dict) -> float | None:
    """Host microseconds from the scan's dispatch to its result being
    ready, per run-slot, over the window's calls."""
    calls = window_calls(record)
    if not calls:
        return None
    ns = sum(c["ns"].get(p, 0) for c in calls for p in SCAN_PARTS)
    return ns / 1e3 / sum(c["slots"] for c in calls)


def finalize_ms(record: dict) -> float | None:
    """Host milliseconds per run from the scan's result being ready to
    the answers: the copy to the host and the post-processing."""
    calls = window_calls(record)
    if not calls:
        return None
    parts = FINALIZE_PARTS[record["entry"]]
    ns = sum(c["ns"].get(p, 0) for c in calls for p in parts)
    return ns / 1e6 / record["runs"]


def trace_path(root: Path, record: dict) -> str | None:
    workload = record.get("workload")
    if not workload:
        return None
    paths = sorted(glob.glob(str(Path(root) / "artifacts" / "bench"
                                 / workload / "trace" / "plugins"
                                 / "profile" / "*" / "*.xplane.pb")))
    return paths[-1] if paths else None


def scope_of(op_name: str) -> str:
    """The stage scope named in an op name (its first segment that is
    one), or ``UNSCOPED``."""
    for seg in op_name.split("/"):
        if seg in SCOPES:
            return seg
    return UNSCOPED


def attribute(events, scopes) -> dict:
    """Self time per scope of the ``(start, dur)`` events of one line,
    ``scopes[i]`` naming event i's. An event's self time is its duration
    less the part of it that the events nested in it cover (events on a
    line nest; one that outlasts its parent is clipped). A nested event
    counts under the outermost enclosing event that has a scope, else
    under its own: the body of a loop shared by several callers (a
    ``searchsorted``) carries the op names of all of them, the loop
    that runs it only its caller's."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [d for _, d in events]
    eff = list(scopes)
    stack: list = []                       # (index, end)
    for i in order:
        s, d = events[i]
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            j, end = stack[-1]
            own[j] -= min(s + d, end) - s
            if eff[j] != UNSCOPED:
                eff[i] = eff[j]
        stack.append((i, s + d))
    out: dict = {}
    for sc, o in zip(eff, own):
        out[sc] = out.get(sc, 0) + o
    return out


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i: int, end: int):
    """``(field number, value)`` of the protobuf message in
    ``buf[i:end]``: an int for a varint, the ``(start, end)`` of a
    length-delimited field, ``None`` for a fixed-width one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _sub(buf, span, field: int):
    """The values of ``field`` in the message at ``span``."""
    return [v for f, v in _fields(buf, *span) if f == field]


def _ints(buf, v) -> list[int]:
    """A repeated integer field's value: packed, or one varint."""
    if isinstance(v, int):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(buf, i)
        out.append(x)
    return out


def hlo_scopes(path: str, module: str, run_name: str) -> dict:
    """Instruction name -> stage scope in the scan's HLO module, from
    the ``/host:metadata`` plane of the trace at ``path``: an
    ``XEventMetadata`` named like the module's execution (``run_name``,
    else the only one whose name starts ``module(``) holds the
    serialized ``HloProto`` as a bytes stat. An instruction whose
    ``metadata.op_name`` names no scope takes the one scope its users
    have, if they agree: XLA leaves the scope off some ops (a
    ``cumsum`` lowers to ops named only ``reduce_window_sum``). A loop,
    conditional or call never does: it runs every stage nested in it,
    and a sweep's slot loop has only the histogram fold that follows it
    (``stream_fold``) for a user. Empty when the trace holds no such
    module.

    Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4
    (map entry value 2); XEventMetadata.name 2, .stats 5; XStat
    .bytes_value 6; HloProto.hlo_module 1; HloModuleProto.computations
    3; HloComputationProto.instructions 2; HloInstructionProto.name 1,
    .opcode 2, .metadata 7, .id 35, .operand_ids 36; OpMetadata.op_name
    2."""
    buf = memoryview(Path(path).read_bytes())

    def text(span):
        return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")

    found = {}
    for plane in _sub(buf, (0, len(buf)), 1):
        fields = list(_fields(buf, *plane))
        if not any(f == 2 and text(v) == "/host:metadata"
                   for f, v in fields):
            continue
        for f, entry in fields:
            if f != 4:
                continue
            for md in _sub(buf, entry, 2):
                names = _sub(buf, md, 2)
                name = text(names[0]) if names else ""
                if name == run_name or name.split("(")[0] == module:
                    found[name] = md
    md = found.get(run_name) or (next(iter(found.values()))
                                 if len(found) == 1 else None)
    if md is None:
        return {}
    names, scope, users, nests = {}, {}, {}, set()
    for stat in _sub(buf, md, 5):
        for proto in _sub(buf, stat, 6):
            for mod in _sub(buf, proto, 1):
                for comp in _sub(buf, mod, 3):
                    for inst in _sub(buf, comp, 2):
                        name, op_name, iid, operands = None, "", None, []
                        nesting = False
                        for f, v in _fields(buf, *inst):
                            if f == 1:
                                name = text(v)
                            elif f == 2:
                                nesting = text(v) in NESTING
                            elif f == 7:
                                ops = _sub(buf, v, 2)
                                op_name = text(ops[0]) if ops else ""
                            elif f == 35:
                                iid = v
                            elif f == 36:
                                operands += _ints(buf, v)
                        names[iid] = name
                        scope[iid] = scope_of(op_name)
                        if nesting:
                            nests.add(iid)
                        for o in operands:
                            users.setdefault(o, []).append(iid)
    changed = True
    while changed:
        changed = False
        for iid, sc in scope.items():
            if sc != UNSCOPED or iid in nests:
                continue
            got = {scope[u] for u in users.get(iid, ())} - {UNSCOPED}
            if len(got) == 1:
                scope[iid] = got.pop()
                changed = True
    return {names[i]: sc for i, sc in scope.items()}


def instruction(event_name: str) -> str:
    """The HLO instruction an op event ran: a TPU op event is named by
    its HLO text (``%sort.12 = (s32[...]) sort(...)``)."""
    return event_name.lstrip("%").split(" ", 1)[0]


def parse(path: str, module: str) -> dict | None:
    """Self nanoseconds per scope of the first execution of ``module``
    on each device plane of the trace at ``path``, summed over devices;
    ``None`` without a device plane that ran the module."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    by_scope: dict = {}
    n_ops, scopes = 0, None
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines or MODULES_LINE not in lines:
            continue
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines[MODULES_LINE].events
                      if e.name.split("(")[0] == module)
        if not runs:
            continue
        m0, m1, run_name = runs[0]
        if scopes is None:
            scopes = hlo_scopes(path, module, run_name)
        events, ev_scopes, scope = [], [], {}
        for e in lines[OPS_LINE].events:
            s = e.start_ns
            if s < m0:
                continue
            if s > m1:
                break
            name = e.name
            if name not in scope:
                scope[name] = scopes.get(instruction(name), UNSCOPED)
            events.append((s, e.duration_ns))
            ev_scopes.append(scope[name])
        n_ops += len(events)
        for sc, ns in attribute(events, ev_scopes).items():
            by_scope[sc] = by_scope.get(sc, 0) + ns
    if scopes is None:
        return None
    return {"self_ns": by_scope, "ops": n_ops,
            "hlo_instructions": len(scopes)}


def stage_shares(root: Path, record: dict) -> dict | None:
    """Share of the scan's recorded self time under each scope (and
    ``UNSCOPED``); ``None`` when there is no device trace of the scan or
    no op carries a scope."""
    path = trace_path(root, record)
    module = SCAN_MODULES.get(record.get("entry"))
    if path is None or module is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _PARSED:
        _PARSED[key] = parse(path, module)
    got = _PARSED[key]
    if got is None:
        return None
    total = sum(got["self_ns"].values())
    if total <= 0 or set(got["self_ns"]) <= {UNSCOPED}:
        return None
    return {k: v / total for k, v in got["self_ns"].items()}


def stage_us_per_slot(root: Path, record: dict,
                      scopes: tuple[str, ...]) -> float | None:
    """Microseconds per run-slot of the scan spent under ``scopes``."""
    shares = stage_shares(root, record)
    wall = scan_wall_us_per_slot(record)
    if shares is None or wall is None:
        return None
    return wall * sum(shares.get(s, 0.0) for s in scopes)
