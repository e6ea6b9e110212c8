"""Profiler trace -> the parsed form every per-layer metric reads.

`capture(dir)` wraps the window in `jax.profiler` with Python tracing off
and host tracing at level 1, which keeps the harness's own
`TraceAnnotation` spans: `WINDOW` around the window and `PUBLISH` at each
chunk boundary. `parse(dir, hlo)` reads the `.xplane.pb` the profiler
wrote. On the TPU each event of a device's "XLA Ops" line is named by its
HLO instruction text; `hlo` is the compiled round program's text
(`compiled.as_text()`), which says which fusions hold a convolution. It
keeps, for each device plane, the leaf events of its "XLA Ops" line (an
event that encloses others on the line is dropped, so that no time
counts twice) and the events of its "XLA Modules" line; from the host
plane it keeps the marker spans. The parsed form is a plain dict, so the
metric readers and their tests need no profiler:

    {"window": [start_ns, end_ns], "publish": [start_ns, ...],
     "devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_ns, dur_ns, class, bytes], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...]}

Op classes (`classify`): "conv" (a convolution, or a fusion whose
computation holds one), "collective", a Pallas kernel named by its
signature ("wagg": one f32 vector out of a stacked f32 matrix;
"q8_encode": int8 codes out; "q8_decode": int8 codes in; "pallas" for
any other), and "xla" for the rest. `bytes` is what a Pallas kernel
reads and writes by its operand and result shapes, 0 for other ops.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re

WINDOW = "bench_window"
PUBLISH = "bench_publish"

_COLLECTIVE = re.compile(r"\s(all-gather|all-reduce|all-to-all|reduce-scatter"
                         r"|collective-permute|collective-broadcast)"
                         r"(-start|-done)?\(")
_FUSION_CALLS = re.compile(r"\sfusion\(.*calls=%?([\w.\-]+)")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
                    r"\[([\d,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}


@contextlib.contextmanager
def capture(logdir: str):
    import jax
    os.makedirs(logdir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def conv_computations(hlo: str) -> set:
    """Names of the computations in `hlo` that hold a convolution,
    directly or through the computations they call. A computation's
    header is an unindented line ending in "{"; its instructions are
    indented."""
    body, calls, cur = {}, {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            words = line.split()
            name = words[1] if words[0] == "ENTRY" else words[0]
            cur = name.lstrip("%")
            body[cur], calls[cur] = False, set()
            continue
        if cur is None:
            continue
        if " convolution(" in line:
            body[cur] = True
        calls[cur].update(_CALLS.findall(line))
    out, changed = {c for c, v in body.items() if v}, True
    while changed:
        changed = False
        for c, callees in calls.items():
            if c not in out and callees & out:
                out.add(c)
                changed = True
    return out


def _shapes(text: str):
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        yield dtype, dims.count(",") + 1 if dims else 0, n * _BYTES[dtype]


def classify(text: str, convs: set) -> tuple:
    """(name, class, bytes) of one "XLA Ops" event's instruction text."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if _COLLECTIVE.search(text):
        return name, "collective", 0
    if " custom-call(" in text and "tpu_custom_call" in text:
        head, _, rest = text.partition(" custom-call(")
        args = rest.split("custom_call_target", 1)[0]
        outs, ins = list(_shapes(head)), list(_shapes(args))
        nbytes = sum(b for _, _, b in outs) + sum(b for _, _, b in ins)
        if any(d == "s8" for d, _, _ in outs):
            kind = "q8_encode"
        elif any(d == "s8" for d, _, _ in ins):
            kind = "q8_decode"
        elif (len(outs) == 1 and outs[0][:2] == ("f32", 1) and ins
              and ins[0][:2] == ("f32", 2)):
            kind = "wagg"
        else:
            kind = "pallas"
        return name, kind, nbytes
    m = _FUSION_CALLS.search(text)
    if (m and m.group(1) in convs) or " convolution(" in text:
        return name, "conv", 0
    return name, "xla", 0


def _leaves(events):
    """Drop events that enclose a later event of the same line."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    keep = [True] * len(events)
    open_ = []                       # indices of events still open
    for i, (_, start, dur, *_) in enumerate(events):
        while open_ and events[open_[-1]][1] + events[open_[-1]][2] <= start:
            open_.pop()
        if open_ and start + dur <= (events[open_[-1]][1]
                                     + events[open_[-1]][2]):
            keep[open_[-1]] = False
        open_.append(i)
    return [e for e, k in zip(events, keep) if k]


def parse(logdir: str, hlo: str) -> dict:
    from jax.profiler import ProfileData
    convs = conv_computations(hlo)
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"window": None, "publish": [], "devices": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = []
                    for e in line.events:
                        name, cls, nbytes = classify(e.name, convs)
                        ops.append([name, int(e.start_ns),
                                    int(e.duration_ns), cls, nbytes])
                    dev["ops"] = _leaves(ops)
                elif line.name == "XLA Modules":
                    dev["modules"] = [[e.name, int(e.start_ns),
                                       int(e.duration_ns)]
                                      for e in line.events]
            if dev["ops"]:
                out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        out["window"] = [int(e.start_ns),
                                         int(e.start_ns + e.duration_ns)]
                    elif e.name == PUBLISH:
                        out["publish"].append(int(e.start_ns))
    out["devices"].sort(key=lambda d: d["name"])
    return out


def summary(logdir: str, per_line: int = 8) -> dict:
    """Planes, lines, event counts and a few events with their stats:
    what a reader needs to see before trusting the parser."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "events": len(evs), "sample": [
                {"name": e.name, "start_ns": e.start_ns,
                 "dur_ns": e.duration_ns,
                 "stats": {k: str(v)[:300] for k, v in e.stats}}
                for e in evs[:per_line]]})
        planes.append({"plane": plane.name, "lines": lines})
    return {"file": paths[-1], "planes": planes}


# --------------------------------------------------------------------------
# reductions shared by the metric readers
# --------------------------------------------------------------------------

def in_window(trace: dict, dev: dict) -> list:
    lo, hi = trace["window"]
    return [o for o in dev["ops"] if o[1] >= lo and o[1] + o[2] <= hi]


def union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(trace: dict, dev: dict) -> int:
    return sum(e - s for s, e in
               union((o[1], o[1] + o[2]) for o in in_window(trace, dev)))


def idle_gaps(trace: dict, dev: dict) -> list:
    """[[start_ns, end_ns], ...] of the window in which nothing ran."""
    lo, hi = trace["window"]
    gaps, t = [], lo
    for s, e in union((o[1], o[1] + o[2]) for o in in_window(trace, dev)):
        if s > t:
            gaps.append([t, s])
        t = max(t, e)
    if hi > t:
        gaps.append([t, hi])
    return gaps


def round_module(trace: dict, dev: dict):
    """[start, end] spans of the module that took most device time in
    the window: the campaign's round program."""
    lo, hi = trace["window"]
    tot = {}
    for name, s, d in dev["modules"]:
        if s >= lo and s + d <= hi:
            tot[name] = tot.get(name, 0) + d
    if not tot:
        return []
    top = max(tot, key=tot.get)
    return [[s, s + d] for name, s, d in dev["modules"]
            if name == top and s >= lo and s + d <= hi]


def ops_in(spans, ops) -> list:
    """The ops that lie inside one of `spans`."""
    spans = sorted(spans)
    starts = [s for s, _ in spans]
    out = []
    for o in ops:
        i = bisect.bisect_right(starts, o[1]) - 1
        if i >= 0 and o[1] + o[2] <= spans[i][1]:
            out.append(o)
    return out


def class_ns(ops, *classes) -> int:
    return sum(o[2] for o in ops if o[3] in classes)


def class_bytes(ops, *classes) -> int:
    return sum(o[4] for o in ops if o[3] in classes)
