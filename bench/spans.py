"""The program's own names in a profiler trace: host spans and device
scopes.

Host spans are the host events named ``app.*`` or ``engine.*`` that the
program's ``obs.span`` writes; each name's seconds are the union of its
intervals inside the window, so nested or repeated spans of one name
count once.  Device scopes are the ``jax.named_scope`` names of the
exchange layer (``relax``, ``exchange``, ``collapse``).  An operation's
``op_name`` metadata (a fusion carries that of its root) is not among
the stats of its event that ``ProfileData`` shows, so it is read from
the optimized HLO the profiler stores with the trace: the ``Hlo Proto``
stat of the ``/host:metadata`` plane's event metadata, one per program,
named like the program's events on the ``XLA Modules`` line.  That
plane's metadata is not exposed by ``ProfileData`` either, so
``trace_op_names`` reads the ``.xplane.pb`` bytes with a small reader of
the protocol buffer wire format.  The innermost of the three names in
an ``op_name`` is the operation's scope, and the seconds of the
innermost operations (``devtrace.leaves``) are summed by scope and
averaged over the devices, as ``devtrace`` averages busy time.

``reduce`` gives ``{"spans": {name: s}, "scopes": {scope: s}}``; an
operation in none of the scopes counts under ``"unscoped"``.
"""
from __future__ import annotations

import bisect

from bench import devtrace

PREFIXES = ("app.", "engine.")
SCOPES = ("relax", "exchange", "collapse")
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = b"/host:metadata"
HLO_PROTO = b"Hlo Proto"


def scope_of(op_name: str | None) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path such as
    ``jit(scan)/while/body/exchange/relax/gather``."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return UNSCOPED


def span_seconds(host_events, lo, hi) -> dict:
    """{name: seconds} of the union of each program span's intervals
    inside ``[lo, hi]``; ``host_events``: [(name, start, end)]."""
    by_name: dict = {}
    for name, s, e in host_events:
        if name.startswith(PREFIXES):
            by_name.setdefault(name, []).append((s, e))
    return {name: devtrace.busy(ivs, lo, hi)
            for name, ivs in sorted(by_name.items())}


def scope_seconds(device_ops: dict, lo, hi) -> dict:
    """{scope: device seconds} of the innermost operations inside
    ``[lo, hi]``, averaged over devices; ``device_ops``: {device:
    [(name, start, end, op_name)]}."""
    total: dict = {}
    for ops in device_ops.values():
        for _, s, e, op_name in devtrace.leaves(ops):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                key = scope_of(op_name)
                total[key] = total.get(key, 0.0) + e - s
    n = max(len(device_ops), 1)
    return {k: v / n for k, v in sorted(total.items())}


def reduce(device_ops: dict, host_events, lo, hi) -> dict:
    return {"spans": span_seconds(host_events, lo, hi),
            "scopes": scope_seconds(device_ops, lo, hi)}


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of a protocol buffer message: varints as
    ints, length-delimited fields as bytes; fixed-width fields skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:                                  # 1: 64-bit, 5: 32-bit
            i += 8 if wire == 1 else 4
            continue
        yield key >> 3, value


def _first(buf: bytes, number: int, default=None):
    return next((v for n, v in _fields(buf) if n == number), default)


def _instruction_op_names(hlo_proto: bytes) -> dict:
    """{instruction: op_name} of an ``HloProto``: hlo_module (1) ->
    computations (3) -> instructions (2) -> name (1), metadata (7) ->
    op_name (2)."""
    out = {}
    for n, comp in _fields(_first(hlo_proto, 1, b"")):
        if n != 3:
            continue
        for m, ins in _fields(comp):
            if m == 2:
                op_name = _first(_first(ins, 7, b""), 2)
                if op_name:
                    out[_first(ins, 1, b"").decode()] = op_name.decode()
    return out


def trace_op_names(xspace: bytes) -> dict:
    """{program: {instruction: op_name}} from the HLO protos of a
    serialized ``XSpace`` (an ``.xplane.pb``): XSpace planes (1) ->
    XPlane name (2), event_metadata (4) and stat_metadata (5), map
    entries holding the value in field 2; XEventMetadata name (2) and
    stats (5); XStat metadata_id (1) and bytes_value (6)."""
    out = {}
    for n, plane in _fields(xspace):
        if n != 1 or _first(plane, 2) != METADATA_PLANE:
            continue
        values = {k: [_first(e, 2, b"") for m, e in _fields(plane)
                      if m == k] for k in (4, 5)}
        stat_ids = {_first(meta, 1): _first(meta, 2) for meta in values[5]}
        for meta in values[4]:
            for m, stat in _fields(meta):
                if m == 5 and stat_ids.get(_first(stat, 1)) == HLO_PROTO:
                    out[_first(meta, 2, b"").decode()] = \
                        _instruction_op_names(_first(stat, 6, b""))
    return out


def _named_ops(lines: dict, op_names: dict):
    """The ``XLA Ops`` events of one device plane as (name, start, end,
    op_name); ``op_names``: {program: {instruction: op_name}}."""
    modules = sorted((ev.start_ns, ev.end_ns, ev.name)
                     for ev in lines.get(MODULES_LINE, ()))
    starts = [m[0] for m in modules]
    out = []
    for ev in lines.get(devtrace.OPS_LINE, ()):
        k = bisect.bisect_right(starts, ev.start_ns) - 1
        table = (op_names.get(modules[k][2], {})
                 if k >= 0 and ev.start_ns < modules[k][1] else {})
        op = devtrace.short_name(ev.name).lstrip("%")
        out.append((ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                    table.get(op)))
    return out


def from_profile(data, op_names: dict) -> dict:
    """``reduce`` over a ``jax.profiler.ProfileData``, inside the span of
    the benchmark's ``bench.window`` annotation; ``op_names`` as
    ``trace_op_names`` gives them."""
    device_ops, host_events, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:") \
                and not plane.name.startswith("/device:CUSTOM"):
            lines = {line.name: list(line.events) for line in plane.lines}
            device_ops[plane.name] = _named_ops(lines, op_names)
        elif plane.name.startswith("/host:"):
            for ev in (ev for line in plane.lines for ev in line.events):
                t = (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                if ev.name == devtrace.WINDOW:
                    window = t[1:]
                host_events.append(t)
    if window is None:
        raise RuntimeError("the trace holds no bench.window annotation")
    return reduce(device_ops, host_events, *window)


def per_iteration(ctx, group: str, name: str):
    """Seconds of span or scope ``name`` (``group`` is ``"spans"`` or
    ``"scopes"``) in the traced window, over the window's PageRank
    iterations: 0 where the program names other spans or scopes but not
    this one, None where it names none (a program without them)."""
    trace, iterations = ctx["trace"], ctx["work"].get("iterations")
    if not trace or not iterations:
        return None
    found = trace.get(group, {})
    if all(key == UNSCOPED for key in found):
        return None
    return found.get(name, 0.0) / iterations


def reader(group: str, name: str):
    """A metric reader of ``per_iteration(ctx, group, name)``."""
    def read(ctx):
        return per_iteration(ctx, group, name)
    read.__doc__ = f"Seconds of {group[:-1]} {name!r} per iteration."
    return read


def read(path: str) -> dict:
    """``reduce`` of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        xspace = f.read()
    return from_profile(ProfileData.from_serialized_xspace(xspace),
                        trace_op_names(xspace))
