#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine this is started on.

    python bench/run.py --workload qwen3-1.7b.chat --seed 7 --seconds 51 --trace 0

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell names its configuration (``bench/configs/<name>.json``,
with the plain reference it names under ``bench/reference/``) and its traffic
mix (``bench/traffic/<mix>.json``); each metric is read by
``bench/metrics/<metric>.py``. Adding a configuration, a mix or a metric
takes new files and entries there, and no edit here.

A run: check the device (a TPU, as many chips as the cell asks for; anything
else exits 2 before any result), make the weights from ``--seed`` on the
device, build the ``Replica`` the configuration states and warm every shape
the traffic uses (that is ``setup_s``), offer the traffic for ``--seconds``,
wait for every answer owed, read the peak memory, free the engine, and
compare a seeded sample of the served tokens with the plain reference. With
``--trace 1`` the engine's tracer is on, a few seconds in the middle of the
window are profiled, and the line carries the per-layer metrics and the
device breakdown instead of the end-to-end metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``: every number compared, with its limit. The same
checks are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------- the spec
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration, mix and metric entries."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"cell": cell,
            "config": load_json(os.path.join(root, conf["file"])),
            "mix": load_json(os.path.join(HERE, "traffic",
                                          f"{cell['traffic']}.json")),
            "end_to_end": e2e, "per_layer": layer}


def reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- model + engine
#: configuration-file keys that fix a ``ModelConfig`` field
MODEL_KEYS = {"num_hidden_layers": "num_layers", "hidden_size": "d_model",
              "num_attention_heads": "num_heads",
              "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta",
              "tie_word_embeddings": "tie_embeddings"}


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    entry it names, with the file's sizes. What the file states and the
    registry fixes otherwise (norm, MLP, qk-norm, window) must agree."""
    from repro.configs import get_config

    cfg = get_config(conf["registry_name"])
    cfg = cfg.replace(**{f: conf[k] for k, f in MODEL_KEYS.items()},
                      dtype=conf["torch_dtype"])
    want = {"norm": conf["norm"], "qk_norm": conf["qk_norm"],
            "mlp_kind": {"silu": "swiglu",
                         "gelu_pytorch_tanh": "gelu"}[conf["hidden_act"]]}
    if conf.get("sliding_window"):
        want["sliding_window"] = conf["sliding_window"]
        want["block_pattern"] = ("sliding",)
    else:
        want["block_pattern"] = ("attn",)
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{conf['registry_name']}: the program's config "
                         f"has {got}, the configuration file states {want}")
    return cfg


class CompileCount:
    """Backend compiles seen by JAX's monitoring, with their times."""

    def __init__(self, monitoring, clock):
        self.clock = clock
        self.times: list[float] = []
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(self.clock())
            self.seconds += secs

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t < hi for t in self.times)


def warm_faults(replica, mix: dict) -> None:
    """Exercise the fault path once before the window: one throwaway
    request, a state fault on its slot once it decodes, its recovery."""
    from repro.serve import Request

    K = replica.window
    assert replica.submit(Request(id=-2, prompt=tuple(range(1, 2 * K + 2)),
                                  max_new_tokens=6 * K)) is None
    hit = False
    while not replica.idle():
        replica.step()
        for s in replica.sched.slots:
            if not hit and s.active and s.pending is None and s.generated:
                hit = replica.inject_state_fault(s.idx) == s.idx
    if not hit or not replica.metrics.faults:
        raise RuntimeError("fault warm-up: no fault was detected")


# ------------------------------------------------------------------ profiling
class Profiler:
    """Profile the part of the window from ``at`` to ``at + trace_s``
    seconds after it opens. The profiler starts just before that part and
    stops once the run is over: its stop writes every event it holds, which
    takes minutes and must not stall the window. ``bench.mark`` annotations
    bound the part reduced."""

    def __init__(self, jax, t_open: float, at: float, trace_s: float):
        self.jax = jax
        self.t_on = t_open + at
        self.t_start = self.t_on - 0.5
        self.t_off = self.t_on + trace_s
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.state = "before"
        self.host = (None, None)
        self.cost = {}

    def _mark(self) -> None:
        with self.jax.profiler.TraceAnnotation("bench.mark"):
            pass

    def tick(self, now: float) -> None:
        if self.state == "before" and now >= self.t_start:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # the annotations are enough
            opts.enable_hlo_proto = False
            t = time.perf_counter()
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.cost["start_s"] = time.perf_counter() - t
            self.state = "started"
        elif self.state == "started" and now >= self.t_on:
            self._mark()
            self.host = (time.monotonic(), None)
            self.state = "on"
        elif self.state == "on" and now >= self.t_off:
            self._mark()
            self.host = (self.host[0], time.monotonic())
            self.state = "off"

    def stop(self) -> None:
        if self.state == "on":
            self.tick(self.t_off)
        if self.state in ("started", "off"):
            t = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.cost["stop_s"] = time.perf_counter() - t
            self.state = "done"


# ----------------------------------------------------------------------- run
def run_cell(spec: dict, *, seed: int, seconds: float, trace: bool,
             chips: int, platform: str = "tpu", plant=None,
             control: bool = False) -> dict:
    """One run of one cell; returns the result object. ``plant(replica)``
    (tests only) breaks the timed path underneath. With ``control`` the
    float8 control takes the program's place in the comparison: the tokens
    float8 ranks first are judged instead of the served ones, so ``correct``
    is the control's, and ``program_gap`` carries the served tokens' gap
    (calibration and tests only)."""
    import jax
    import numpy as np

    from repro.models import build_model
    from repro.obs.trace import Tracer
    from repro.serve import EngineConfig, Replica

    import correct as correct_mod
    import drive as drive_mod
    import trace_reduce
    from traffic import make_plan
    from weights import make_weights

    clock = time.monotonic
    compiles = CompileCount(jax.monitoring, clock)
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if dev.platform != platform or len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} {platform} chip(s); "
                         f"JAX reports {len(devices)} {dev.platform} "
                         f"({dev.device_kind})")
    conf, mix, cell = spec["config"], spec["mix"], spec["cell"]
    cfg = model_config(conf)
    split = {"to_jax_s": time.perf_counter() - T_START}

    t = time.perf_counter()
    layout = jax.eval_shape(lambda: build_model(cfg).init(
        jax.random.PRNGKey(0)))
    params = make_weights(layout, seed, conf["init_std"])
    jax.block_until_ready(params)
    split["weights_s"] = time.perf_counter() - t

    t = time.perf_counter()
    tracer = Tracer(clock=clock) if trace else None
    replica = Replica(cfg, params, config=EngineConfig(**conf["engine"]),
                      tracer=tracer, clock=clock)
    if plant is not None:
        plant(replica)
    split["build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    if mix.get("faults"):
        warm_faults(replica, mix)
    replica.warmup(max_new=2 * replica.window)
    split["warmup_s"] = time.perf_counter() - t
    split["compile_s"] = compiles.seconds
    setup_s = time.perf_counter() - T_START

    plan = make_plan(mix, seed, seconds, cfg.vocab_size)
    rng = np.random.default_rng([int(seed), 1])
    prof = None
    if trace:
        t_open = clock() + plan.warm_s
        prof = Profiler(jax, t_open, at=min(mix.get("trace_at_s", 20.0),
                                            0.4 * seconds),
                        trace_s=min(mix.get("trace_s", 6.0), 0.5 * seconds))
        host_wait = {"s": 0.0}
        real_get = jax.device_get

        def timed_get(x):
            t0 = time.perf_counter()
            try:
                return real_get(x)
            finally:
                host_wait["s"] += time.perf_counter() - t0

        jax.device_get = timed_get
    try:
        run = drive_mod.drive(
            replica, plan, seconds=seconds, drain_s=mix["drain_s"], rng=rng,
            clock=clock,
            annotate=(jax.profiler.TraceAnnotation if trace else None),
            on_tick=(prof.tick if prof else None))
    finally:
        if trace:
            jax.device_get = real_get
            prof.stop()
    in_window = compiles.between(run.t_open, run.t_close)
    stats = dev.memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    device["memory_peak_bytes"] = peak
    say(f"memory: peak {peak} B of {stats.get('bytes_limit')} B; "
        f"compiles in the window: {in_window}")

    owed = [r for r in run.records.values()
            if run.t_open <= r.t_due < run.t_close]
    failed = [r for r in owed if r.status != "ok"]
    art = {"run": run, "owed": owed, "setup_s": setup_s, "config": conf,
           "window": replica.window, "device_kind": dev.device_kind}
    if trace:
        art["tracer"] = tracer.events()
        art["host_wait_s"] = host_wait["s"]
    metrics_out = {}
    wanted = spec["per_layer"] if trace else spec["end_to_end"]

    # free the engine before the reference runs: the peak is already read
    del replica
    gc.collect()

    if trace:
        t = time.perf_counter()
        art["device"] = trace_reduce.reduce_dir(prof.dir, chips=chips)
        art["profile_host"] = prof.host
        shutil.rmtree(prof.dir, ignore_errors=True)
        device["busy_s"] = art["device"]["busy_s"]
        device["window_s"] = art["device"]["window_s"]
        say(f"trace reduced in {time.perf_counter() - t:.1f} s; profiler "
            f"{json.dumps(prof.cost)}; device programs "
            f"{json.dumps(art['device']['programs'])}")
    for m in wanted:
        v = reader(m["name"])(art)
        if v is not None:
            metrics_out[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- correctness: a seeded sample of what the window served
    done = {r.rid: (run.prompts[r.rid], r.tokens) for r in run.records.values()
            if r.status == "ok"}
    hit = {f.rid for f in run.faults}
    sample = correct_mod.pick_sample(
        done, np.random.default_rng([int(seed), 2]),
        tokens=mix["check_tokens"], must=tuple(hit))
    t = time.perf_counter()
    ref = correct_mod.load_reference(conf["reference"])
    res = correct_mod.compare(ref, params, conf, [done[i] for i in sample],
                              control=control)
    ref_s = time.perf_counter() - t
    limit = conf["limits"]["gap_sigma"]
    gap = res["control_gap"] if control else res["gap"]
    checks = {
        "gap_sigma": {"value": gap, "limit": limit},
        "unanswered": {"value": len(failed), "limit": 0},
        "sampled_tokens": {"value": res["tokens"],
                           "limit": mix["check_tokens"]},
    }
    if mix.get("faults"):
        checks["recovered_in_sample"] = {
            "value": len(hit & set(sample) & set(done)), "limit": 1}
    ok = (limit is not None and gap <= limit and not failed
          and res["tokens"] >= mix["check_tokens"]
          and all(c["value"] >= c["limit"] for k, c in checks.items()
                  if k == "recovered_in_sample"))
    say(f"setup: {json.dumps(split)}; setup_s {setup_s:.3f}")
    say(f"window: {len(owed)} due, {len(failed)} not answered ok; "
        f"{run.steps} steps, {run.windows} windows; generator late "
        f"p90 {np.percentile(run.late_s, 90) if run.late_s else 0:.4f} s; "
        f"{len(run.faults)} faults, "
        f"{sum(f.t_heal is not None for f in run.faults)} recovered")
    say(f"reference: {len(sample)} requests, {res['tokens']} served tokens, "
        f"argmax agrees at {res['argmax_agree']}; {ref_s:.1f} s")
    if control:
        say(f"control (fp8) in the program's place: widest gap "
            f"{gap:.6f} sigma; the served tokens' {res['gap']:.6f}")
    out = {"correct": bool(ok), "attempted": len(owed),
           "failed": len(failed), "metrics": metrics_out, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": art["device"]["device_ops"],
                            "idle_gaps": art["device"]["idle_gaps"]}
    if control:
        out["program_gap"] = res["gap"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        say(f"bench: no program under {ROOT}/src; run from a checkout")
        return 2
    for p in (os.path.join(ROOT, "src"), HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = cell_spec(args.workload)
    # the compile cache lives at a fixed path inside the checkout
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # every entry kept, none evicted: a size cap from the environment
    # turns on eviction, which failed to write entries on the chip
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        out = run_cell(spec, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), chips=spec["cell"]["chips"])
    except SystemExit as e:
        say(str(e))
        return 2
    for name, c in out["checks"].items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
