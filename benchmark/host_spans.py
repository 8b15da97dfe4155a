"""The engine's ``engine.*`` annotations in a profiler trace (.xplane.pb), on
the clock of the device's operations: which host line holds them, how long
each phase ran, which phase of the engine's loop thread enclosed each stretch
in which the device ran nothing, and the device's time under each
``jax.named_scope`` of the step. ``breakdown.idle_gaps`` of a traced run is
``idle_by_span`` (through ``layer_metrics/_common.py``); the rest is by hand,
on a trace that ``run.py --keep-trace`` left under ``.bench_tmp/trace/``:

    python3 benchmark/host_spans.py <file.xplane.pb>
"""

from __future__ import annotations

import sys

LOOP_PHASES = ("engine.admin", "engine.plan", "engine.launch", "engine.wait",
               "engine.emit", "engine.yield")
# jax.named_scope names of the jitted step (models/llama.py, llm/sampling.py,
# llm/engine.py)
SCOPES = ("qkv", "attn", "kv_write", "oproj", "ffn", "moe", "logits", "sample",
          "logprobs")


def engine_spans(profile) -> list:
    """(name, seq, start_s, end_s, host line) of every ``engine.*`` event on
    a host plane, in order of start. ``seq`` is the launch the span belongs
    to (None where the annotation carries none)."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for number, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("engine."):
                    seq = dict(ev.stats).get("seq")
                    out.append((ev.name, seq, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                "{}#{}".format(line.name, number)))
    return sorted(out, key=lambda s: s[2])


def merged(intervals: list) -> list:
    """Sorted disjoint (start, end) covering the same time."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def idle_by_phase(busy: list, spans: list) -> dict:
    """Seconds of device idle time (the gaps between the merged ``busy``
    intervals) inside each loop-thread phase of ``spans``; what no phase
    encloses is under ``None``."""
    busy = merged(busy)
    loop = [(s, e, name) for name, _, s, e, _ in spans if name in LOOP_PHASES]
    out = {}
    for (_, g0), (g1, _) in zip(busy, busy[1:]):
        left = g1 - g0
        for s, e, name in loop:
            cut = min(e, g1) - max(s, g0)
            if cut > 0:
                out[name] = out.get(name, 0.0) + cut
                left -= cut
        out[None] = out.get(None, 0.0) + max(left, 0.0)
    return out


NO_SPAN = "no_cycle"


def innermost(spans: list) -> list:
    """Sorted disjoint (start, end, name): over every stretch that some span
    covers, the name of the covering span that started last. On one thread
    that is the innermost annotation; ``engine.upload`` / ``engine.enqueue``
    of the dispatch worker start inside the loop thread's ``engine.launch``
    and take its place while they run, ``engine.readback`` that of
    ``engine.wait``."""
    edges = sorted({t for _, _, s, e, _ in spans for t in (s, e)})
    by_start = sorted((s, e, name) for name, _, s, e, _ in spans)
    out, live, k = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while k < len(by_start) and by_start[k][0] <= lo:
            live.append(by_start[k])
            k += 1
        live = [x for x in live if x[1] > lo]
        if live:
            name = max(live, key=lambda x: (x[0], -x[1]))[2]   # the shorter of a tie
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, name)
            else:
                out.append((lo, hi, name))
    return out


def idle_by_span(gaps: list, spans: list) -> list:
    """For every idle gap (start, length) of the device, in order of start:
    {name: seconds} of the gap under each innermost ``engine.*`` span; what
    no span encloses is under ``NO_SPAN`` (the engine was in no cycle)."""
    cover = innermost(spans)
    out, k = [], 0
    for g0, length in sorted(gaps):
        g1, parts, left = g0 + length, {}, length
        while k < len(cover) and cover[k][1] <= g0:
            k += 1
        i = k
        while i < len(cover) and cover[i][0] < g1:
            cut = min(cover[i][1], g1) - max(cover[i][0], g0)
            if cut > 0:
                parts[cover[i][2]] = parts.get(cover[i][2], 0.0) + cut
                left -= cut
            i += 1
        if left > 0:
            parts[NO_SPAN] = parts.get(NO_SPAN, 0.0) + left
        out.append(parts)
    return out


def summed(parts: list) -> dict:
    """{name: seconds} over all the gaps of ``idle_by_span``."""
    out = {}
    for split in parts:
        for name, secs in split.items():
            out[name] = out.get(name, 0.0) + secs
    return out


def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint or
    a fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            width = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + width], "little"), i + width
        yield key >> 3, value


def device_ms_by_scope(path: str, scopes=SCOPES) -> dict:
    """Milliseconds of device operations under each ``jax.named_scope`` of
    the step, summed over the ``XLA Ops`` lines of the TPU planes. The scope
    is a component of the operation's ``op_name``, which a TPU trace keeps as
    stat ``tf_op`` of the event's METADATA
    (``jit(_ragged_paged_step)/while/body/closed_call/attn/
    ragged_paged_attention/pallas_call:``); ``jax.profiler.ProfileData``
    shows only an event's own stats, so the file is read as protobuf fields
    (XSpace, tsl/profiler/protobuf/xplane.proto). A ``while`` holds the
    operations of its body and is left out; what no scope of ``scopes``
    encloses is under ``None``."""
    out = {}
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for number, plane in _fields(space):
        if number != 1:                                  # XSpace.planes
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for number, value in _fields(plane):
            if number == 2:                              # XPlane.name
                name = bytes(value).decode()
            elif number == 3:                            # .lines
                lines.append(value)
            elif number == 4:                            # .event_metadata
                event_meta.update(_map_entry(value))
            elif number == 5:                            # .stat_metadata
                for key, meta in _map_entry(value).items():
                    stat_names[key] = _string(meta, 2)
        if not name.startswith("/device:TPU:"):
            continue
        scope_of = {}
        for key, meta in event_meta.items():
            stats = {}
            for number, stat in _fields(meta):
                if number == 5:                          # XEventMetadata.stats
                    st = dict(_fields(stat))
                    text = st.get(5)                     # XStat.str_value
                    stats[stat_names.get(st.get(1))] = (
                        bytes(text).decode() if text is not None
                        else stat_names.get(st.get(7), ""))   # .ref_value
            if stats.get("hlo_category") == "while":
                continue
            inside = [p for p in stats.get("tf_op", "").rstrip(":").split("/")
                      if p in scopes]
            scope_of[key] = inside[0] if inside else None
        for line in lines:
            if _string(line, 2) != "XLA Ops":            # XLine.name
                continue
            for number, event in _fields(line):
                if number != 4:                          # .events
                    continue
                ev = dict(_fields(event))                # metadata_id, duration_ps
                if ev.get(1) in scope_of:
                    scope = scope_of[ev[1]]
                    out[scope] = out.get(scope, 0.0) + ev.get(3, 0) * 1e-9
    return out


def _map_entry(entry) -> dict:
    """{key: value message} of one entry of a protobuf map<int64, message>
    (a negative key stays the unsigned varint it is written as, like the
    ``metadata_id`` that refers to it)."""
    fields = dict(_fields(entry))
    return {fields.get(1, 0): fields.get(2, b"")}


def _string(message, number: int) -> str:
    return next((bytes(v).decode() for n, v in _fields(message) if n == number), "")


def _dump(path: str) -> None:
    from benchmark import xplane

    profile = xplane.load(path)
    spans = engine_spans(profile)
    rows = {}
    for name, _, s, e, line in spans:
        n, total, lo, hi, lines = rows.get(name, (0, 0.0, s, e, set()))
        rows[name] = (n + 1, total + e - s, min(lo, s), max(hi, e), lines | {line})
    for name, (n, total, lo, hi, lines) in sorted(rows.items()):
        print("{:16s} {:5d} events {:9.3f} ms  from {:.4f} to {:.4f} s  on {}".format(
            name, n, total * 1e3, lo, hi,
            lines.pop() if len(lines) == 1 else "{} host lines".format(len(lines))))
    planes = xplane.device_planes(profile)
    ops = xplane.line_events(planes[0], xplane.OPS_LINE) if planes else []
    if not ops:
        print("no device operations in this trace")
        return
    busy = merged([(s, s + d) for _, s, d in ops])
    print("device operations from {:.4f} to {:.4f} s, busy {:.3f} s".format(
        busy[0][0], busy[-1][1], sum(e - s for s, e in busy)))
    idle = idle_by_phase(busy, spans)
    print("device idle {:.3f} s, by the loop-thread phase that enclosed it:".format(
        sum(idle.values())))
    for name, secs in sorted(idle.items(), key=lambda kv: -kv[1]):
        print("  {:16s} {:.3f} s".format(name or "(no phase)", secs))
    gaps = [(e0, s1 - e0) for (_, e0), (s1, _) in zip(busy, busy[1:])]
    inner = summed(idle_by_span(gaps, spans))
    print("the same idle time by the innermost span (breakdown.idle_gaps):")
    for name, secs in sorted(inner.items(), key=lambda kv: -kv[1]):
        print("  {:16s} {:.3f} s".format(name, secs))
    by_scope = device_ms_by_scope(path)
    print("device operations {:.1f} ms, by the named scope in their op_name:".format(
        sum(by_scope.values())))
    for name, ms in sorted(by_scope.items(), key=lambda kv: -kv[1]):
        print("  {:16s} {:9.1f} ms".format(name or "(no scope)", ms))


if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    _dump(sys.argv[1])
