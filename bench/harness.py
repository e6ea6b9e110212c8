"""One run of one benchmark cell, driven by `BENCHMARK.json`.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic and limits are files found by name
(`configs/<config>.json`, `traffic/<traffic>.json`,
`limits/<workload>.json`); each per-layer metric is a reader in
`metrics/<metric>.py`. A run:

1. needs a TPU with as many chips as the cell asks for; anything else
   exits non-zero with no result;
2. keeps JAX's compilation cache at `<checkout>/.jax_cache`;
3. makes the images, the weights and the FL seed from `--seed`
   (generate.py) and builds the program's `Scenario` from them;
4. set-up: `check_rounds` rounds from the seed's round-0 state, one
   `run_campaign` call per chunk (`chunk_rounds`), the window's own call
   and program: the first compiles it, or loads it from the cache; the
   model after each is kept for the check;
5. the window: ONE `run_campaign` call of N rounds (whole chunks, sized
   from `--seconds` and the traffic's round estimate), started from the
   set-up's state, with a publish hook at every chunk that only notes
   the time; no compile may happen inside it;
6. reads peak device memory, drops the program's state, and compares
   the set-up rounds with the plain reference (check.py);
7. prints the numbers compared beside their limits on stderr, and the
   result as the last line of stdout.
"""
from __future__ import annotations

import gc
import gzip
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SystemExit(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def cell(bench: dict, workload: str) -> dict:
    """Everything one workload names, loaded from its files."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; valid: "
                         f"{sorted(by_name)}")
    w = by_name[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    limits = load_json(os.path.join(BENCH, "limits", f"{workload}.json"))
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return {"workload": w, "cfg": cfg, "traffic": traffic, "limits": limits,
            "per_layer": per_layer, "end_to_end": end_to_end}


def metric_reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def flops_module(cfg: dict):
    path = os.path.join(BENCH, "flops", f"{cfg['flops']}.py")
    spec = importlib.util.spec_from_file_location(f"bench_flops_{cfg['flops']}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chip(chips: int):
    """The devices the cell runs on; exits when JAX finds no TPU or too
    few chips. There is no CPU fallback."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"bench: JAX finds no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX's default device is "
                     f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"peaks.json ({sorted(k for k in table if k != 'source')})")
    return table[kind]


def window_rounds(seconds: float, traffic: dict) -> int:
    """Rounds in the window: whole chunks, as many as the traffic's
    round estimate fits into `seconds` (at least one)."""
    chunk = traffic["chunk_rounds"]
    per_chunk = traffic["round_ms_estimate"] * chunk / 1000.0
    return chunk * max(1, round(seconds / per_chunk))


def scenario(cfg: dict, traffic: dict, data, tree, fl_seed: int):
    from repro.core.mobility import MobilityModel
    from repro.core.scenario import Scenario
    topo = cfg["topology"]
    return Scenario(topology=topo["kind"],
                    topology_kwargs=dict(topo["kwargs"]),
                    client=cfg["client"], aggregator=cfg["aggregator"],
                    arch=cfg["model"]["arch"],
                    mobility=MobilityModel(**cfg["mobility"]),
                    data=data, global_tree=tree,
                    n_vehicles=cfg["n_vehicles"],
                    vehicles_per_round=traffic["vehicles_per_round"],
                    batch_size=cfg["batch_size"],
                    local_iters=cfg["local_iters"], lr=cfg["lr"],
                    rounds=cfg["lr_horizon_rounds"],
                    momentum=cfg["momentum"],
                    weight_decay=cfg["weight_decay"],
                    tau_alpha=cfg["tau_alpha"], tau_beta=cfg["tau_beta"],
                    codec=traffic["codec"], seed=fl_seed)


class GcLog:
    """Python's garbage collections inside a `with` block:
    [[generation, start_s, seconds], ...] on `time.perf_counter`."""

    def __init__(self):
        self.events, self._start = [], None

    def _cb(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            now = time.perf_counter()
            self.events.append([info["generation"], self._start,
                                now - self._start])

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


def model_of(state):
    """The model the check compares (reference.initial_model says which):
    the RSU models under handover, else the global model."""
    if "rsu_models" in (state.topo or {}):
        return list(state.topo["rsu_models"])
    return state.global_tree


def warm_planner_shapes(sc, traffic: dict):
    """Handover planning computes Eq. 11 upload weights eagerly, one
    program per upload-group size; a long campaign meets every size from
    1 to the cohort, a single chunk only some. Run the same eager call
    for each size so that none compiles inside the window."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import aggregation
    from repro.core.topology import HandoverMultiRSU
    if isinstance(sc.topology, HandoverMultiRSU):
        for k in range(1, traffic["vehicles_per_round"] + 1):
            np.asarray(aggregation.flsimco_weights(
                jnp.asarray(np.ones(k, np.float32))))


class Hook:
    """The publish hook: notes the time of each chunk boundary and, in a
    traced run, marks it in the trace. Touches no device array."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.times = []

    def __call__(self, rnd, tree):
        self.times.append((rnd, time.perf_counter()))
        if self.traced:
            import jax
            from tracing import PUBLISH
            with jax.profiler.TraceAnnotation(PUBLISH):
                pass


def round_program_hlo(sc, state, chunk: int) -> str:
    """The compiled text of the campaign's scan program for one chunk,
    lowered from the same arguments the window passed, so that the
    compile cache returns the program the window ran: it names the
    fusions the trace's ops call."""
    import jax
    import jax.numpy as jnp

    from repro.core import engine
    xs_list = engine._plan_chunk(state, sc, chunk)[0]
    xs = jax.tree.map(lambda *ls: jnp.stack(ls), *xs_list)
    carry = engine._carry_of(state, sc)
    rep = engine._replicated(sc)
    if rep is not None:
        carry = jax.device_put(carry, rep)
    dstack = engine._data_stack(sc)
    if rep is not None:
        dstack = jax.device_put(dstack, rep)
    t = time.perf_counter()
    text = engine.campaign_callables(sc)["scan"].lower(
        dstack, carry, xs).compile().as_text()
    print(f"bench: round program text in {time.perf_counter() - t:.2f} s",
          file=sys.stderr)
    return text


def device_info(devs) -> dict:
    """Platform, kind, count, and the memory peak of the fullest chip:
    the allocator's peak in use plus the peak it reserved for programs'
    scratch (XLA's temporaries live there on the TPU, and
    `peak_bytes_in_use` alone leaves them out)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    print(f"bench: memory_stats of {devs[0]}: {devs[0].memory_stats()}",
          file=sys.stderr)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t0: float) -> dict:
    """Run one cell and return the result object; `t0` is the process's
    start on `time.perf_counter`'s clock."""
    import jax
    import numpy as np

    import check
    import generate
    import reference
    from repro.analysis.guards import track_compiles
    from repro.core import engine
    from repro.core.scenario import run_campaign

    c = cell(benchmark(), workload)
    cfg, traffic = c["cfg"], c["traffic"]
    chips = c["workload"]["chips"]
    devs = require_chip(chips)
    out_dir = os.path.join(OUT, workload)
    os.makedirs(out_dir, exist_ok=True)

    s = generate.seeds(seed)
    data = generate.images(cfg, s["data"])
    tree0 = generate.weights(cfg, s["weights"])
    sc = scenario(cfg, traffic, data, tree0, s["fl"])
    state0 = sc.init_state()
    chunk = traffic["chunk_rounds"]

    warm_planner_shapes(sc, traffic)
    # set-up: the first rounds from the seed, each through the window's
    # own call and program; the check follows them
    st, warm = state0, {"losses": [], "velocities": [], "lrs": [],
                        "models": []}
    for _ in range(traffic["check_rounds"] // chunk):
        st, hist = run_campaign(sc, st, rounds=chunk, publish_every=chunk)
        for r in hist:
            warm["losses"].append(r["loss"])
            warm["velocities"].append(r["velocities"])
            warm["lrs"].append(r["lr"])
        warm["models"].append(model_of(st))
    warm["models"] = jax.device_get(warm["models"])
    # the rest of the warm-up, to the first round of every kind the
    # window meets (handover: a region sync)
    rest = traffic["warmup_rounds"] - traffic["check_rounds"]
    if rest > 0:
        st, _ = run_campaign(sc, st, rounds=rest, publish_every=chunk)
        jax.block_until_ready(st.global_tree)

    n_rounds = window_rounds(seconds, traffic)
    hook = Hook(traced)
    trace_dir = os.path.join(out_dir, f"trace-{seed}")
    tracer = None
    if traced:
        import shutil

        from tracing import WINDOW, capture
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = capture(trace_dir)
        tracer.__enter__()
    # the set-up's objects (JAX's caches, the images, the schedule) move
    # to the permanent generation: a full collection inside the window
    # then scans only what the window makes (collections are logged)
    gc.collect()
    gc.freeze()
    pauses = GcLog()
    setup_s = time.perf_counter() - t0
    with track_compiles() as compiles, pauses:
        if traced:
            ann = jax.profiler.TraceAnnotation(WINDOW)
            ann.__enter__()
        t_start = time.perf_counter()
        st, hist = run_campaign(sc, st, rounds=n_rounds,
                                publish_every=chunk, publish=hook)
        jax.block_until_ready(st.global_tree)
        t_end = time.perf_counter()
        if traced:
            ann.__exit__(None, None, None)
    if tracer is not None:
        tracer.__exit__(None, None, None)
    if compiles.backend_compiles:
        raise SystemExit(f"bench: {compiles.backend_compiles} compile(s) "
                         f"inside the window")
    window_s = t_end - t_start
    device = device_info(devs)
    hlo = round_program_hlo(sc, st, chunk) if traced else None
    losses = [r["loss"] for r in hist]
    failed = sum(1 for x in losses if not np.isfinite(x))
    with open(os.path.join(out_dir, f"chunks-{seed}.json"), "w") as f:
        json.dump({"window_start": t_start, "window_end": t_end,
                   "publish": hook.times, "rounds": n_rounds,
                   "losses": losses, "gc": pauses.events}, f)

    del st, hist, state0, sc
    engine.reset_engine_caches()
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference.run(cfg, traffic, data, tree0, s["fl"],
                        traffic["check_rounds"], devices=list(devs))
    got = check.numbers(warm, ref, reference.initial_model(cfg, tree0))
    with open(os.path.join(out_dir, f"check-{seed}.json"), "w") as f:
        json.dump(got, f)
    checks = check.compare(got, c["limits"])
    ref_s = time.perf_counter() - t_ref

    result = {"correct": all(v["value"] <= v["limit"]
                             for v in checks.values()),
              "attempted": n_rounds, "failed": failed}
    if traced:
        import tracing as tr
        with gzip.open(os.path.join(out_dir, f"round-{seed}.hlo.gz"),
                       "wt") as f:
            f.write(hlo)
        parsed = tr.parse(trace_dir, hlo)
        if not parsed["devices"] or parsed["window"] is None:
            raise SystemExit("bench: the trace holds no device op or no "
                             "window marker")
        with open(os.path.join(out_dir, f"trace-{seed}.json"), "w") as f:
            json.dump(parsed, f)
        with open(os.path.join(out_dir, f"trace-summary-{seed}.json"),
                  "w") as f:
            json.dump(tr.summary(trace_dir), f)
        ctx = Context(parsed, cfg, traffic, n_rounds,
                      peaks_for(device["kind"]), chips)
        metrics = {}
        for m in c["per_layer"]:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = ctx.busy_s()
        device["window_s"] = ctx.window_s()
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = ctx.breakdown()
    else:
        values = {"round_ms": window_s * 1000.0 / n_rounds,
                  "peak_hbm_gib": device["memory_peak_bytes"] / 2**30,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in c["end_to_end"]}
        result["device"] = device
    result["checks"] = checks
    print(f"bench: {workload} seed {seed}: window {window_s:.3f} s for "
          f"{n_rounds} rounds, set-up {setup_s:.2f} s, reference "
          f"{ref_s:.2f} s, {len(pauses.events)} collections in the window "
          f"({sum(e[2] for e in pauses.events):.3f} s)", file=sys.stderr)
    return result


class Context:
    """What a per-layer metric reader gets: the parsed trace of the
    window and the cell's shapes, peaks and round count."""

    def __init__(self, trace, cfg, traffic, rounds, peaks, chips):
        import tracing as tr
        self.tr = tr
        self.trace, self.cfg, self.traffic = trace, cfg, traffic
        self.rounds, self.peaks, self.chips = rounds, peaks, chips
        self.flops = flops_module(cfg)

    def window_s(self) -> float:
        lo, hi = self.trace["window"]
        return (hi - lo) / 1e9

    def busy_s(self) -> float:
        devs = self.trace["devices"]
        return sum(self.tr.busy_ns(self.trace, d) for d in devs) \
            / len(devs) / 1e9

    def round_ops(self, dev) -> list:
        """The ops of the round program on `dev` inside the window."""
        return self.tr.ops_in(self.tr.round_module(self.trace, dev),
                              self.tr.in_window(self.trace, dev))

    def breakdown(self) -> dict:
        """The ten ops that took most device time (averaged over the
        chips), and the ten longest idle gaps of the first chip, each
        named by what the host was doing then."""
        tot = {}
        for d in self.trace["devices"]:
            for o in self.tr.in_window(self.trace, d):
                key = f"{o[0]} ({o[3]})"
                tot[key] = tot.get(key, 0) + o[2]
        n = len(self.trace["devices"])
        ops = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
        d0 = self.trace["devices"][0]
        spans = sorted(self.tr.round_module(self.trace, d0))
        pub = self.trace["publish"]
        gaps = []
        for s, e in self.tr.idle_gaps(self.trace, d0):
            if not spans or e <= spans[0][0]:
                label = "window start: data-stack build and upload, first plan"
            elif s >= spans[-1][1]:
                label = "window end: history fetch"
            elif any(a <= s and e <= b for a, b in spans):
                label = "inside the round program"
            elif any(s <= p <= e for p in pub):
                label = "chunk boundary: history fetch and publish"
            else:
                label = "chunk boundary: planning, schedule stacking, dispatch"
            gaps.append([label, (e - s) / 1e9])
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
                "idle_gaps": gaps[:10]}
