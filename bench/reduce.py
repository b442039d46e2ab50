"""From a profiler trace of the window to the numbers per-layer readers use.

The trace is the `.xplane.pb` that `jax.profiler` writes.  In it:

- the window is the host span `bench.window`; each round is a `bench.round`
  span, and the drivers mark what the host does inside a round with
  further `bench.*` spans (batches, schedule, dispatch, fetch);
- the devices are the planes named `/device:TPU:<id>`, and their
  operations the events of each plane's `XLA Ops` line.  A trace without
  such planes (one recorded on the CPU, for the tests) takes as device
  operations the host events that carry an `hlo_op`;
- an operation's layer is the named scope in its op metadata, read from the
  compiled round program's text (`op_name="jit(round_jit)/.../dfedpgp.mix/
  ..."`), for the operations of that program.

Busy time is the union of a device's operation intervals inside the window;
a scope's time is the union of its operations' intervals, so an operation
nested in another (a loop and its body) counts once; the breakdown's
operations are ranked by self time, their time less their nested ones'.
An idle gap is named after the innermost `bench.*` host span that covers
most of it.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

SCOPES = ("dfedpgp.local", "dfedpgp.mix", "dfedpgp.gather",
          "dfedpgp.scatter")
COLLECTIVE = re.compile(r"collective-permute|all-gather|all-reduce|"
                        r"reduce-scatter|all-to-all|\bsend\b|\brecv\b|"
                        r"ppermute", re.I)
TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
TOP = 10


def find_trace(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def op_names(hlo_text: str) -> tuple:
    """(module name, {instruction name: op_name metadata}) of a compiled
    program's text."""
    module = re.search(r"^HloModule\s+([\w.\-]+)", hlo_text, re.M)
    table = {}
    for m in re.finditer(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=[^\n]*?"
                         r"op_name=\"([^\"]*)\"", hlo_text, re.M):
        table[m.group(1)] = m.group(2)
    return (module.group(1) if module else ""), table


def scope_of(op_name: str) -> str:
    for s in SCOPES:
        if s in op_name:
            return s
    return "other"


# ---------------------------------------------------------------------------
# intervals
# ---------------------------------------------------------------------------
def union(intervals) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def subtract(a_list, b_list) -> list:
    """The parts of union a_list that no interval of union b_list covers."""
    out, j = [], 0
    for a, b in a_list:
        cur = a
        while j < len(b_list) and b_list[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_list) and b_list[k][0] < b:
            s, e = b_list[k]
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def gaps(busy, lo, hi) -> list:
    return subtract([(lo, hi)], busy)


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------
def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _op_name(name: str) -> str:
    """`%fusion.3 = f32[...] fusion(...)` (a TPU op event's name is the
    instruction's text) or `fusion.3` -> `fusion.3`."""
    m = re.match(r"\s*%?([\w.\-]+)", name)
    return m.group(1) if m else name


def _module_name(name: str) -> str:
    """`jit_round_jit(123)` or `jit_round_jit` -> `jit_round_jit`."""
    return re.split(r"[\s(]", name.strip(), maxsplit=1)[0]


def _events(pd):
    """(host spans [(name, start, end)], {device: [(op, module, s, e)]}).

    On a TPU plane an operation's module is the `XLA Modules` event that
    holds it in time; on the CPU it is the event's `hlo_module` stat."""
    host, devices = [], collections.defaultdict(list)
    planes = list(pd.planes)
    tpu = [p for p in planes if TPU_PLANE.match(p.name)]
    for plane in planes:
        if plane in tpu:
            dev = int(TPU_PLANE.match(plane.name).group(1))
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           _module_name(ev.name))
                          for ev in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            for ev in lines.get("XLA Ops", []):
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= e else ""
                devices[dev].append((_op_name(ev.name), mod, s, e))
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name.startswith("bench."):
                    host.append((ev.name, s, e))
                if not tpu:
                    st = _stats(ev)
                    if "hlo_op" in st:
                        devices[0].append((st["hlo_op"],
                                           str(st.get("hlo_module", "")),
                                           s, e))
    return host, dict(devices)


def self_times(events) -> list:
    """Each event's duration less the part its nested events cover (a loop
    op's event holds its body's ops).  `events` sorted by start."""
    own = [e - s for s, e, *_ in events]
    stack = []
    for i, (s, e, *_) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def exposed_collective(events) -> float:
    """Time in which the innermost operation running is a collective: its
    self time, which no computation nested in it covers.  (An operation
    that holds a collective in time, such as a loop around it, computes
    nothing while the collective waits.)  `events` sorted by start, each
    (start, end, op, ...)."""
    return float(sum(own for (_, _, op, *_), own in
                     zip(events, self_times(events))
                     if COLLECTIVE.search(op)))


def _module_matches(event_module: str, module: str) -> bool:
    return bool(module) and (event_module == module
                             or event_module.startswith(module + "(")
                             or event_module.startswith(module + "."))


def reduce(trace_path: str, hlo_text: str, device_ids) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(trace_path)
    host, devices = _events(pd)
    windows = [(s, e) for n, s, e in host if n == "bench.window"]
    if not windows:
        raise ValueError(f"{trace_path}: no bench.window span")
    lo, hi = windows[0]
    rounds = sum(1 for n, s, e in host
                 if n == "bench.round" and s >= lo and e <= hi)
    module, table = op_names(hlo_text or "")
    ids = [d for d in device_ids if d in devices] or sorted(devices)[:1]
    if not ids:
        raise ValueError(f"{trace_path}: no device operations")

    per_dev = []
    op_time = collections.Counter()
    for d in ids:
        busy_iv, coll_iv = [], []
        scope_iv = collections.defaultdict(list)
        events = sorted((max(s, lo), min(e, hi), op, mod)
                        for op, mod, s, e in devices[d] if e > lo and s < hi)
        for (s, e, op, mod), own in zip(events, self_times(events)):
            busy_iv.append((s, e))
            ours = _module_matches(mod, module) or (not mod and op in table)
            scope = scope_of(table.get(op, "")) if ours else "other"
            scope_iv[scope].append((s, e))
            if COLLECTIVE.search(op):
                coll_iv.append((s, e))
            label = f"{scope}:{op}" if ours else f"{mod or '?'}:{op}"
            op_time[label] += own / len(ids)
        per_dev.append({
            "busy": union(busy_iv),
            "scope_s": {k: length(union(v)) * 1e-9
                        for k, v in scope_iv.items()},
            "collective_s": length(union(coll_iv)) * 1e-9,
            "exposed_collective_s": exposed_collective(events) * 1e-9})

    n = len(per_dev)
    avg = lambda key: sum(p[key] for p in per_dev) / n
    scope_s = collections.Counter()
    for p in per_dev:
        for k, v in p["scope_s"].items():
            scope_s[k] += v / n

    idle = collections.Counter()
    spans = sorted((s, e, name) for name, s, e in host
                   if name not in ("bench.window",) and e > lo and s < hi)
    for a, b in gaps(per_dev[0]["busy"], lo, hi):
        best, best_key = None, None
        for s, e, name in spans:
            ov = min(b, e) - max(a, s)
            if ov <= 0:
                continue
            key = (ov, -(e - s))          # most overlap, then innermost
            if best_key is None or key > best_key:
                best, best_key = name, key
        idle[best or "host:outside bench spans"] += (b - a) * 1e-9

    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(length(p["busy"]) for p in per_dev) / n * 1e-9,
        "rounds": rounds,
        "devices": n,
        "scope_s": dict(scope_s),
        "collective_s": avg("collective_s"),
        "exposed_collective_s": avg("exposed_collective_s"),
        "breakdown": {
            "device_ops": [[k, v * 1e-9] for k, v in
                           op_time.most_common(TOP)],
            "idle_gaps": [[k, v] for k, v in idle.most_common(TOP)],
        },
    }
