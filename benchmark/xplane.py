"""Reduce a profiler trace (.xplane.pb) to device busy time, operation times
and idle gaps. Reads with ``jax.profiler.ProfileData`` and nothing else.

A TPU trace has one plane per chip, ``/device:TPU:<n>``. Its line ``XLA Ops``
holds one event per device operation (start and duration in nanoseconds) and
its line ``XLA Modules`` one event per launched program. Busy time is the
union of the operation intervals, so overlapping events (a ``while`` and the
operations inside it) count once.

    python3 benchmark/xplane.py <file.xplane.pb>     # print what is in it
"""

from __future__ import annotations

import glob
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str):
    """The newest .xplane.pb under a ``jax.profiler`` trace directory."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_planes(profile) -> list:
    return [p for p in profile.planes if p.name.startswith("/device:TPU:")]


def line_events(plane, line_name: str) -> list:
    """(name, start_s, duration_s) of every event on the plane's line."""
    out = []
    for line in plane.lines:
        if line.name != line_name:
            continue
        for ev in line.events:
            out.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


def union_seconds(events: list, lo: float = None, hi: float = None) -> float:
    """Total length of the union of the events' intervals, cut to [lo, hi]."""
    spans = sorted((s, s + d) for _, s, d in events)
    total, cur_s, cur_e = 0.0, None, None
    for s, e in spans:
        if lo is not None:
            s, e = max(s, lo), max(e, lo)
        if hi is not None:
            s, e = min(s, hi), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: list, min_s: float = 0.0) -> list:
    """(start_s, length_s) of every stretch in which no event ran, between
    the first event's start and the last event's end."""
    out = []
    cur_e = None
    for s, e in sorted((s, s + d) for _, s, d in events):
        if cur_e is not None and s - cur_e > min_s:
            out.append((cur_e, s - cur_e))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def by_name(events: list) -> list:
    """[(name, total seconds, count)] by total seconds, largest first."""
    acc = {}
    for name, _, d in events:
        t, n = acc.get(name, (0.0, 0))
        acc[name] = (t + d, n + 1)
    return sorted(((k, t, n) for k, (t, n) in acc.items()),
                  key=lambda x: -x[1])


_HLO = re.compile(r"^%(?P<name>[^ ]+) = (?P<out>\(?[a-z0-9]+\[[0-9,]*\])?.*?"
                  r"(?P<op>[a-z][a-z0-9\-]*)\(")
# operations that only hold others: their time is their children's
_CONTAINERS = ("while", "conditional", "call", "async-start", "async-done")


def short_name(text: str) -> str:
    """``%fusion.184 = bf16[352,14336]{...} fusion(...)`` ->
    ``fusion.184_fusion_bf16_352_14336``: the operation as XLA names it, its
    opcode and its result's type and shape, at most 64 characters."""
    m = _HLO.match(text)
    if not m:
        return re.sub(r"[^A-Za-z0-9_.\-]+", "_", text)[:64]
    out = re.sub(r"[^a-z0-9]+", "_", m.group("out") or "").strip("_")
    return "_".join(x for x in (m.group("name"), m.group("op"), out) if x)[:64]


def is_container(text: str) -> bool:
    m = _HLO.match(text)
    return bool(m) and m.group("op") in _CONTAINERS


def is_kernel(name: str) -> bool:
    """A Pallas kernel reaches the trace as a custom call."""
    low = name.lower()
    return "custom-call" in low or "custom_call" in low or "pallas" in low


def reduce_trace(path: str) -> dict:
    """What the per-layer metrics read: the traced window's length, busy
    seconds averaged over the chips, per-operation totals, idle gaps, and
    the kernels' seconds, with the host's ``engine.*`` annotations beside
    them. ``window_s`` runs from the first operation's
    start to the last operation's end over all chips."""
    from benchmark import host_spans

    profile = load(path)
    planes = device_planes(profile)
    if not planes:
        return {"planes": [p.name for p in profile.planes], "devices": 0}
    per_plane = [(p.name, line_events(p, OPS_LINE), line_events(p, MODULES_LINE))
                 for p in planes]
    used = [(n, ops, mods) for n, ops, mods in per_plane if ops]
    if not used:
        return {"planes": [p.name for p in profile.planes], "devices": 0}
    lo = min(s for _, ops, _ in used for _, s, _ in ops)
    hi = max(s + d for _, ops, _ in used for _, s, d in ops)
    busy = [union_seconds(ops) for _, ops, _ in used]
    ops0, mods0 = used[0][1], used[0][2]
    # a loop or a call spans the operations inside it: leave it out of the
    # per-operation totals (the union for busy time is unchanged by it)
    names = by_name([(short_name(n), s, d) for n, s, d in ops0
                     if not is_container(n)])
    return {
        "devices": len(used),
        "t_lo": lo, "t_hi": hi,
        "window_s": hi - lo,
        "busy_s": sum(busy) / len(busy),
        "ops": names,
        "kernel_s": sum(t for n, t, _ in names if is_kernel(n)),
        "modules": by_name(mods0),
        "n_launches": len(mods0),
        "gaps": gaps(ops0),
        # the host's engine.* annotations, on the same clock as the gaps
        "host_spans": host_spans.engine_spans(profile),
    }


def _dump(path: str) -> None:
    profile = load(path)
    for plane in profile.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line {!r}: {} events".format(line.name, len(evs)))
            for ev in evs[:4]:
                stats = {k: (str(v)[:60]) for k, v in ev.stats}
                print("    {!r} start_ns={} dur_ns={} stats={}".format(
                    ev.name[:90], ev.start_ns, ev.duration_ns, stats))
    red = reduce_trace(path)
    for key in ("devices", "window_s", "busy_s", "kernel_s", "n_launches"):
        print(key, red.get(key))
    for row in (red.get("ops") or [])[:12]:
        print("  op", row)
    for row in (red.get("modules") or [])[:8]:
        print("  module", row)


if __name__ == "__main__":
    _dump(sys.argv[1])
