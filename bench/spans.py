"""The program's own spans and scopes, as a `--trace 1` run records them.

`run_campaign` (src/repro/core/engine.py) writes host spans with
`jax.profiler.TraceAnnotation`: the names in `SPANS`, one
`campaign.chunk` step per chunk (`step_num` = the chunk's first round)
and its children, each with `round` = that number. The round program's
ops carry `jax.named_scope` names (`SCOPES`) in the `op_name` of their
compiled metadata; a fusion carries its root's. The benchmark pins these
names here and imports nothing from the program for them.

`tracing.parse` keeps neither, so `attach(ctx)` adds two keys to the
parsed trace of a reader's context, once per run:

    "spans":  [[name, start_ns, dur_ns, step, attrs], ...]
    "scopes": {instruction name: op_name}

It reads the profile and the round program's text that the harness
wrote for the run (the newest trace directory under the harness's
output whose window marker is the parsed window). A trace that holds
the keys already (a recorded test trace) is used as it is. A program
without spans or scopes gives empty keys, and the readers return None.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
import statistics

import harness
import tracing

CHUNK = "campaign.chunk"
SPANS = ("campaign.data_stack", CHUNK, "campaign.plan", "campaign.carry",
         "campaign.dispatch", "campaign.fetch", "campaign.state",
         "campaign.publish", "campaign.checkpoint")
SCOPES = ("blur", "client_step", "batch_norm", "codec", "aggregate",
          "region_sync")

_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bmetadata=\{[^\n]*?'
                      r'\bop_name="((?:[^"\\]|\\.)*)"', re.M)
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def read_spans(logdir: str) -> tuple:
    """(window, spans) from the `.xplane.pb` under `logdir`: the harness's
    window marker as [start_ns, end_ns] (None without one) and every
    host event named in SPANS."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    pd = ProfileData.from_file(paths[-1])
    window, spans = None, []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == tracing.WINDOW:      # as tracing.parse has it
                    window = [int(e.start_ns),
                              int(e.start_ns + e.duration_ns)]
                elif e.name in SPANS:
                    attrs = {k: v for k, v in e.stats if k != "_r"}
                    step = attrs.pop("step_num", attrs.get("round"))
                    spans.append([e.name, int(e.start_ns),
                                  int(e.duration_ns), step, attrs])
    spans.sort(key=lambda s: (s[1], -s[2]))
    return window, spans


def read_scopes(hlo: str) -> dict:
    """{instruction name: op_name} of every instruction of the compiled
    text `hlo` that has an op_name."""
    return {m.group(1): m.group(2) for m in _OP_NAME.finditer(hlo)}


def under(op_name: str, *scopes) -> bool:
    """Whether `op_name` lies under one of `scopes`: a path segment equal
    to it, or wrapped by transformations, as `vmap(client_step)` or
    `transpose(jvp(batch_norm))`."""
    for seg in re.split(r"[/;]", op_name):
        while seg not in scopes:
            m = _WRAPPED.match(seg)
            if m is None:
                break
            seg = m.group(1)
        else:
            return True
    return False


def _run_files(window):
    """(spans, round program text) of the harness run whose window
    marker is `window`, or None."""
    dirs = glob.glob(os.path.join(harness.OUT, "*", "trace-*", ""))
    dirs.sort(key=os.path.getmtime, reverse=True)
    for d in dirs:
        if not glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True):
            continue
        found, spans = read_spans(d)
        if found != window:
            continue
        d = d.rstrip(os.sep)
        hlo = os.path.join(os.path.dirname(d), os.path.basename(d).replace(
            "trace-", "round-", 1) + ".hlo.gz")
        text = ""
        if os.path.exists(hlo):
            with gzip.open(hlo, "rt") as f:
                text = f.read()
        return spans, text
    return None


def attach(ctx) -> dict:
    """The parsed trace of `ctx`, with "spans" and "scopes" added."""
    trace = ctx.trace
    if "spans" not in trace:
        got = _run_files(trace["window"])
        spans, hlo = got if got else ([], "")
        trace["spans"], trace["scopes"] = spans, read_scopes(hlo)
    return trace


# --------------------------------------------------------------------------
# reductions shared by the readers
# --------------------------------------------------------------------------

def chunk_ms(ctx, *names):
    """Median over the window's chunks of the time in the child spans
    `names`, over the chunk's rounds (ms). A median, so that the one long
    stall a profiler adds to a traced window does not set it."""
    spans = attach(ctx)["spans"]
    lo, hi = ctx.trace["window"]
    per = []
    for c in spans:
        if c[0] != CHUNK or c[1] < lo or c[1] + c[2] > hi:
            continue
        ns = sum(s[2] for s in spans
                 if s[0] in names and s[3] == c[3]
                 and c[1] <= s[1] and s[1] + s[2] <= c[1] + c[2])
        per.append(ns / c[4]["rounds"])
    return statistics.median(per) / 1e6 if per else None


def scope_ms(ctx, *scopes):
    """Device time per round (ms) of the round program's ops that lie
    under one of `scopes`, averaged over the chips."""
    hit = {name for name, op in attach(ctx)["scopes"].items()
           if under(op, *scopes)}
    devs = ctx.trace["devices"]
    tot = sum(o[2] for d in devs for o in ctx.round_ops(d) if o[0] in hit)
    return tot / len(devs) / ctx.rounds / 1e6 if tot else None


def idle_gaps(ctx, top: int = 10) -> list:
    """The `top` longest idle gaps of the first chip as [span, seconds],
    each named by the innermost program span that covers its midpoint on
    the host (None where no span does)."""
    spans = attach(ctx)["spans"]
    gaps = sorted(ctx.tr.idle_gaps(ctx.trace, ctx.trace["devices"][0]),
                  key=lambda g: g[0] - g[1])[:top]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        inner = [sp for sp in spans if sp[1] <= mid <= sp[1] + sp[2]]
        name = min(inner, key=lambda sp: sp[2])[0] if inner else None
        out.append([name, (e - s) / 1e9])
    return out
