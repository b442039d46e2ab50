"""From a profiler trace of the window to the program's nested scopes and to
the host span behind every idle instant.

`bench.reduce` names a device operation after the outer `dfedpgp.*` scope
of its op metadata, read from the compiled round's text, and an idle gap
after the `bench.*` host span that covers most of it.  This module reads
the same `.xplane.pb` for two finer numbers:

- `subscope_s`: seconds a chip spends under each scope of `SUBSCOPES`,
  scopes nested in those `bench.reduce` knows (`dfedpgp.u_update` lies in
  `dfedpgp.local`).  An operation's op name comes from the optimized HLO
  that the profiler stores with the trace (the `/host:metadata` plane), so
  no compiled text is needed.  Each scope's time is the union of its
  operations' intervals in the window, averaged over the chips.
- `idle_by_span`: device 0's idle time in the window, cut at every host
  span's edges, each piece given to the innermost span over it: the
  harness's `bench.*` spans (but the window's own) and the program's
  `dfedpgp.host.*` spans; a piece no span covers goes to `OUTSIDE`.  The
  values sum to the window less device 0's busy time.

A trace of a program without these scopes or spans gives no entry for
them, and a reader of them returns nothing.
"""
from __future__ import annotations

import bisect
import collections
import functools
import sys
from pathlib import Path

from bench import reduce

SUBSCOPES = ("dfedpgp.u_update",)
HARNESS_SPAN = "bench."
PROGRAM_SPAN = "dfedpgp.host."
OUTSIDE = "host:outside spans"
METADATA_PLANE = "/host:metadata"
# where bench/run.py leaves a traced run's trace
TRACES = Path(__file__).resolve().parents[1] / ".bench_out" / "trace"


# ---------------------------------------------------------------------------
# the HLO the profiler stores with the trace
# ---------------------------------------------------------------------------
def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b, span=None):
    """(field number, value) of one protobuf message in `b[lo:hi]`: an int
    for a varint, a (lo, hi) slice for a length-delimited field, None for
    a fixed-width one."""
    i, hi = span if span is not None else (0, len(b))
    while i < hi:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield field, (i, i + n)
            i += n
        elif wire in (1, 5):
            yield field, None
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _hlo_op_names(b, proto) -> dict:
    """{instruction name: op_name metadata} of a serialized HloProto
    (hlo_module = 1; its computations = 3; their instructions = 2, each
    with name = 1 and metadata = 7, whose op_name = 2)."""
    table = {}
    for f, module in _fields(b, proto):
        if f != 1:
            continue
        for f2, comp in _fields(b, module):
            if f2 != 3:
                continue
            for f3, inst in _fields(b, comp):
                if f3 != 2:
                    continue
                name = op_name = None
                for f4, v in _fields(b, inst):
                    if f4 == 1:
                        name = _text(b, v)
                    elif f4 == 7:
                        op_name = next((_text(b, m) for f5, m in
                                        _fields(b, v) if f5 == 2), None)
                if name and op_name:
                    table[name] = op_name
    return table


def program_op_names(raw: bytes) -> dict:
    """{(program id, module name): {instruction name: op_name}} from the
    HLO protos in an XSpace's metadata plane: each event metadata there is
    one program (id = 1, name = 2, `jit_round_fn(<id>)`) whose stats (5)
    hold its HloProto as bytes (6)."""
    b = memoryview(raw)
    out = {}
    for f, plane in _fields(b):                  # XSpace.planes = 1
        if f != 1:
            continue
        name, entries = None, []
        for g, v in _fields(b, plane):
            if g == 2:                           # XPlane.name
                name = _text(b, v)
            elif g == 4:                         # XPlane.event_metadata
                entries.append(v)
        if name != METADATA_PLANE:
            continue
        for entry in entries:                    # map entry: key 1, value 2
            meta = next((v for g, v in _fields(b, entry) if g == 2), None)
            if meta is None:
                continue
            pid, module, table = None, "", {}
            for g, v in _fields(b, meta):
                if g == 1:
                    pid = v
                elif g == 2:
                    module = reduce._module_name(_text(b, v))
                elif g == 5:
                    for h, x in _fields(b, v):
                        if h == 6:
                            table.update(_hlo_op_names(b, x))
            if pid is not None and table:
                out[(pid, module)] = table
    return out


# ---------------------------------------------------------------------------
# the trace's events
# ---------------------------------------------------------------------------
def _program_id(module: str):
    """`jit_round_fn(3469363036114191666)` -> 3469363036114191666."""
    head, _, rest = module.partition("(")
    digits = rest.split(")", 1)[0]
    return int(digits) if head and digits.isdigit() else None


def _events(pd):
    """(host spans [(name, start, end)],
    {device: [(op, (program id, module name), s, e)]}),
    the spans those of the harness and the program.  As in `bench.reduce`,
    a trace without TPU planes takes as device operations the host events
    that carry an `hlo_op`."""
    spans, devices = [], collections.defaultdict(list)
    planes = list(pd.planes)
    tpu = {p.name for p in planes if reduce.TPU_PLANE.match(p.name)}
    for plane in planes:
        if plane.name in tpu:
            dev = int(reduce.TPU_PLANE.match(plane.name).group(1))
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           (_program_id(ev.name),
                            reduce._module_name(ev.name)))
                          for ev in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            for ev in lines.get("XLA Ops", []):
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                i = bisect.bisect_right(starts, s) - 1
                prog = (mods[i][2] if i >= 0 and mods[i][1] >= e
                        else (None, ""))
                devices[dev].append((reduce._op_name(ev.name), prog, s, e))
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name.startswith((HARNESS_SPAN, PROGRAM_SPAN)):
                    spans.append((ev.name, s, e))
                if not tpu:
                    st = reduce._stats(ev)
                    if "hlo_op" in st:
                        prog = (st.get("program_id"),
                                str(st.get("hlo_module", "")))
                        devices[0].append((st["hlo_op"], prog, s, e))
    return spans, dict(devices)


# ---------------------------------------------------------------------------
# idle time, given to the innermost span
# ---------------------------------------------------------------------------
def _innermost_key(span):
    s, e, name = span
    # the shortest span is the innermost; on a tie the later start, then
    # the program's own span (it is the one called inside)
    return (e - s, -s, not name.startswith(PROGRAM_SPAN))


def idle_by_span(idle, spans, outside: str = OUTSIDE) -> dict:
    """{span name: ns} of the idle intervals `idle` (sorted, disjoint), each
    cut at the edges of `spans` [(start, end, name)] and every piece given
    to the innermost span that covers it, or to `outside`."""
    edges = sorted({x for s, e, _ in spans for x in (s, e)})
    order = sorted(spans)
    owner, active, j = [], [], 0
    for a in edges[:-1]:                      # segment [edges[i], edges[i+1])
        while j < len(order) and order[j][0] <= a:
            active.append(order[j])
            j += 1
        active = [sp for sp in active if sp[1] > a]
        owner.append(min(active, key=_innermost_key)[2] if active
                     else outside)
    out = collections.Counter()
    for a, b in idle:
        cuts = ([a] + edges[bisect.bisect_right(edges, a):
                            bisect.bisect_left(edges, b)] + [b])
        for x, y in zip(cuts, cuts[1:]):
            i = bisect.bisect_right(edges, x) - 1    # the segment holding x
            out[owner[i] if 0 <= i < len(owner) else outside] += y - x
    return dict(out)


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def reduce_spans(trace_path: str, device_ids=None) -> dict:
    """{window_s, busy_s (device 0's), rounds, subscope_s, idle_by_span}
    of one traced window."""
    from jax.profiler import ProfileData
    raw = Path(trace_path).read_bytes()
    pd = ProfileData.from_serialized_xspace(raw)
    spans, devices = _events(pd)
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not windows:
        raise ValueError(f"{trace_path}: no bench.window span")
    lo, hi = windows[0]
    rounds = sum(1 for n, s, e in spans
                 if n == "bench.round" and s >= lo and e <= hi)
    wanted = set(devices if device_ids is None else device_ids)
    ids = sorted(d for d in devices if d in wanted)
    if not ids:
        raise ValueError(f"{trace_path}: no device operations")
    names = program_op_names(raw)
    # an executable loaded from the persistent cache may run under another
    # program id than the one its HLO is stored under (so on the CPU):
    # then its module's name finds it
    by_module = collections.defaultdict(dict)
    for (_, module), table in names.items():
        by_module[module].update(table)
    by_id = {pid: table for (pid, _), table in names.items()}

    sub = collections.Counter()
    for d in ids:
        iv = collections.defaultdict(list)
        for op, (pid, module), s, e in devices[d]:
            if e <= lo or s >= hi:
                continue
            table = by_id.get(pid) or by_module.get(module, {})
            op_name = table.get(op, "")
            for scope in SUBSCOPES:
                if scope in op_name:
                    iv[scope].append((max(s, lo), min(e, hi)))
        for scope, v in iv.items():
            sub[scope] += reduce.length(reduce.union(v)) * 1e-9 / len(ids)

    busy = reduce.union((max(s, lo), min(e, hi)) for _, _, s, e
                        in devices[ids[0]] if e > lo and s < hi)
    inner = [(max(s, lo), min(e, hi), n) for n, s, e in spans
             if n != "bench.window" and e > lo and s < hi]
    idle = idle_by_span(reduce.gaps(busy, lo, hi), inner)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": reduce.length(busy) * 1e-9,
            "rounds": rounds,
            "subscope_s": dict(sub),
            "idle_by_span": {k: v * 1e-9 for k, v in
                             sorted(idle.items(), key=lambda kv: -kv[1])}}


@functools.lru_cache(maxsize=2)
def _reduced(path: str, chips: int) -> dict:
    return reduce_spans(path, range(chips))


def of_run(ctx):
    """The reduction of the trace a traced run of `bench/run.py` just left
    under `TRACES`, for a per-layer reader's context; None where there is
    no trace, or where its window is not the one `ctx` was reduced from."""
    try:
        path = reduce.find_trace(TRACES)
    except FileNotFoundError:
        return None
    try:
        out = _reduced(path, ctx["chips"])
    except (ValueError, IndexError, RuntimeError) as e:
        # a trace this module cannot read gives no numbers; the run's
        # other metrics stand
        print(f"bench.spans: {path}: {e!r}", file=sys.stderr)
        return None
    if abs(out["window_s"] - ctx["trace"]["window_s"]) > 1e-9:
        return None
    return out
