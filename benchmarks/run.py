"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmarks/run.py --selfcheck

A cell is an entry of BENCHMARK.json `workloads`: {name, config, traffic,
chips, why}. This file knows no cell, configuration, traffic mix or metric by
name: it resolves `config` to benchmarks/configs/<config>.json, `traffic` to
benchmarks/traffic/<traffic>.json, runs the traffic file's `kind` through
benchmarks/<kind>_cell.py, and computes per-layer metrics by calling every
reader in benchmarks/metrics/*.py. Adding a cell, a configuration, a traffic
mix or a metric is adding files and BENCHMARK.json entries (README.md).

Needs a TPU listed in peaks.json: with none, or with fewer chips than the
cell asks for, it exits non-zero naming the device and prints no result.
`--rehearse-cpu` is the test-only seam (an argument, not a fallback): it runs
the same code at the tiny sizes the files give under "rehearsal", and its last
line carries the names of the metrics it would report and no value.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse
import importlib.util
import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 6.0  # length of the profiled extension after the window


def log(*parts) -> None:
    print("[bench]", *parts, flush=True)


def load_json(*rel) -> dict:
    with open(os.path.join(HERE, *rel)) as f:
        return json.load(f)


def load_module(path: str):
    """Import a benchmark file by path, once (file names may hold dots)."""
    name = "bench_" + os.path.relpath(path, HERE)[:-3].replace(os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def percentile(xs, p: float) -> float:
    """Nearest-rank percentile of a non-empty sequence."""
    s = sorted(xs)
    k = max(0, min(len(s) - 1, int(-(-p * len(s) // 100)) - 1))
    return s[k]


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def replace_nested(obj, tree: dict):
    """dataclasses.replace, recursing where the new value is a dict."""
    import dataclasses

    kw = {k: replace_nested(getattr(obj, k), v) if isinstance(v, dict) else v
          for k, v in tree.items()}
    return dataclasses.replace(obj, **kw)


class Phases:
    """Where set-up went: consecutive named phases from process start."""

    def __init__(self):
        self.t = T_PROCESS
        self.items = []

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.items.append((name, now - self.t))
        self.t = now

    def summary(self) -> dict:
        out: dict = {}
        for k, v in self.items:
            out[k] = round(out.get(k, 0.0) + v, 3)
        return out


class CompileWatch:
    """Counts XLA compile requests (jax.monitoring), so a compile inside the
    measured window is seen: a persistent-cache hit still stalls the host."""

    def __init__(self):
        self.count = 0

    def _on(self, name, duration, **kw):
        if name == COMPILE_EVENT:
            self.count += 1

    def install(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self


class Context:
    """What a cell runner gets: the resolved files, the arguments, helpers."""

    def __init__(self, args, cell, config, traffic):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed = int(args.seed)
        self.seed32 = self.seed % 2147483647  # PRNGKey / numpy seeds fit 31 bits
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearsal = bool(args.rehearse_cpu)
        self.chips = int(cell["chips"])
        self.phases = Phases()
        self.compiles = CompileWatch()
        self.trace_dir = os.path.join(HERE, ".work", "trace")
        self.trace_seconds = TRACE_SECONDS if not self.rehearsal else 1.0
        self.log = log
        self.t_process = T_PROCESS
        self.percentile = percentile

    @staticmethod
    def load(fn: str):
        """A benchmark module by its file name under benchmarks/."""
        return load_module(os.path.join(HERE, fn))

    def repo_config(self):
        """The repo preset the configuration file names, with the file's
        `overrides` applied (nested, by dataclass field); refuses to run if
        the result disagrees with the sizes the file states under `model`."""
        import dataclasses

        from midgpt_tpu.config import load_config

        config = replace_nested(load_config(self.config["repo_config"]), self.config.get("overrides", {}))
        ran = dataclasses.asdict(config.model_config)
        for k, v in self.config.get("model", {}).items():
            if not self.rehearsal and ran.get(k) != v:
                raise SystemExit(f"configs/{self.cell['config']}.json says model.{k}={v!r} "
                                 f"but the resolved repo config runs {ran.get(k)!r}")
        return config

    def start_trace(self):
        """Profiler on, python tracer off (it would record every call of the
        host loop); returns the host-clock reading of the `bench.sync` mark."""
        import shutil

        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        t_sync = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.sync"):
            pass
        return t_sync

    def stop_trace(self, t_sync: float, spans) -> dict:
        """Profiler off; the trace reduced (reduce.summarize) with the host
        spans moved onto its clock through the sync mark."""
        import jax

        jax.profiler.stop_trace()
        reduce = self.load("reduce.py")
        trace = reduce.load_xplane(reduce.find_xplane(self.trace_dir))
        sync = [m for m in trace["marks"] if m[0] == "bench.sync"]
        if not sync:
            raise RuntimeError("the trace holds no bench.sync mark: host spans cannot be placed")
        offset = sync[-1][1] / 1e9 - t_sync
        summary = reduce.summarize(trace, spans, offset)
        summary["trace"] = trace
        n_ops = sum(d["n_ops"] for d in summary["devices"])
        last = max((s + d for dev in summary["devices"] for _, s, d in dev["ops"]), default=summary["lo"])
        log(f"trace coverage: {n_ops} device op events inside bench.window, the last ending "
            f"{(last - summary['lo']) / 1e6:.0f} ms into its {summary['window_ns'] / 1e6:.0f} ms "
            f"(a trace that ends early was cut by the profiler, which keeps ~4.96 M events: PERF.md section 7 row 28)")
        return summary


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json (have: "
                     f"{[w['name'] for w in bench['workloads']]})")


def metric_applies(m: dict, cell_name: str) -> bool:
    return "workloads" not in m or cell_name in m["workloads"]


def device_record(jax, trace_summary=None) -> dict:
    devs = jax.local_devices()
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # a backend without memory_stats reports 0
            stats = {}
        if d is devs[0]:
            log("memory_stats of device 0:", json.dumps({k: int(v) for k, v in stats.items()}))
        # The TPU allocator counts live buffers (`peak_bytes_in_use`) apart from
        # the region it reserves for the programs' temporaries
        # (`peak_bytes_reserved`); free = limit - both. The chip's peak is the sum.
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0)))
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": jax.device_count(), "memory_peak_bytes": peak}
    if trace_summary is not None:
        rec["busy_s"] = trace_summary["busy_ns_mean"] / 1e9
        rec["window_s"] = trace_summary["window_ns"] / 1e9
    return rec


def per_layer_metrics(run: dict) -> dict:
    """Every reader in metrics/*.py gets the run record; what each returns is
    merged. A reader that finds nothing to read returns nothing."""
    out = {}
    mdir = os.path.join(HERE, "metrics")
    for fn in sorted(os.listdir(mdir)):
        if fn.endswith(".py"):
            try:
                got = load_module(os.path.join(mdir, fn)).read(run)
            except Exception:  # one broken reader must not cost the run its other metrics
                log(f"metrics/{fn} failed and its metrics are left out:\n{traceback.format_exc()}")
                continue
            if got:
                out.update({k: v for k, v in got.items() if v is not None})
    return out


def selfcheck() -> int:
    reduce = load_module(os.path.join(HERE, "reduce.py"))
    fx = load_json("fixtures", "trace_small.json")
    want = load_json("fixtures", "trace_small.expected.json")
    s = reduce.summarize(fx, [tuple(x) for x in fx["spans"]], fx["offset"])
    k_ns, k_calls = reduce.kernel_time(s, fx, want["kernel_name"], want["kernel_info"])
    got = {
        "window_ns": s["window_ns"], "busy_ns": s["busy_ns_mean"],
        "idle_share": 1 - s["busy_ns_mean"] / s["window_ns"],
        "kernel_ns": k_ns, "kernel_calls": k_calls,
        "gap_owner": want["gap_owner"],
        "gap_owner_s": s["gaps_s"].get(want["gap_owner"], 0.0),
    }
    bad = []
    for k, v in want["values"].items():
        g = got[k]
        ok = g == v if isinstance(v, (int, str)) else abs(g - v) <= 1e-9 * max(1.0, abs(v))
        if not ok:
            bad.append(f"{k}: expected {v!r}, got {g!r}")
    # arithmetic spot checks, values worked out by hand beside the fixture
    arith = load_module(os.path.join(HERE, "arithmetic.py"))
    for chk in want["arithmetic"]:
        g = getattr(arith, chk["fn"])(*chk["args"])
        g = list(g) if isinstance(g, tuple) else g
        if json.dumps(g) != json.dumps(chk["expect"]):
            bad.append(f"{chk['fn']}{chk['args']}: expected {chk['expect']}, got {g}")
    lg = load_module(os.path.join(HERE, "loadgen.py"))
    for tf in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        spec = load_json("traffic", tf)
        if spec.get("kind") == "serve":
            a = lg.Traffic(spec, 1, 50304).multiset()
            b = lg.Traffic(spec, 2**31 + 12345, 50304).multiset()
            if a != b:
                bad.append(f"traffic {tf}: multiset differs between seeds")
    for line in bad:
        log("selfcheck FAILED:", line)
    log("selfcheck", "failed" if bad else "passed", json.dumps(got))
    return 1 if bad else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--selfcheck", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="test-only: tiny sizes on the CPU backend; reports no value")
    args = ap.parse_args()
    if args.selfcheck:
        return selfcheck()
    if not args.workload:
        ap.error("--workload is required")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = find_cell(bench, args.workload)
    if args.seconds is None:
        args.seconds = bench["run_seconds"]
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_NUM_CPU_DEVICES"] = str(cell["chips"])
        config = merge(config, config.get("rehearsal", {}))
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    sys.path.insert(0, ROOT)
    try:
        import midgpt_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"benchmarks/run.py: the system under test is not in this checkout ({e})",
              file=sys.stderr)
        return 1

    import jax

    ctx = Context(args, cell, config, traffic)
    devs = jax.devices()
    dev = devs[0]
    peaks = None
    if not ctx.rehearsal:
        arith = ctx.load("arithmetic.py")
        try:
            if dev.platform != "tpu":
                raise KeyError(f"platform {dev.platform!r} is not a TPU")
            peaks = arith.peaks_for(dev.device_kind)
        except KeyError as e:
            print(f"benchmarks/run.py measures a TPU listed in peaks.json: {e.args[0]} "
                  f"(found platform {dev.platform!r}, device_kind {dev.device_kind!r}, "
                  f"{len(devs)} device(s))", file=sys.stderr)
            return 1
    if len(devs) < ctx.chips:
        print(f"cell {cell['name']!r} asks for {ctx.chips} chip(s); found {len(devs)} "
              f"({dev.platform!r}, {dev.device_kind!r})", file=sys.stderr)
        return 1
    ctx.peaks = peaks
    ctx.devices = devs[: ctx.chips]
    from midgpt_tpu.utils import compile_cache

    ctx.cache_stats = compile_cache.enable()  # before the first compile
    ctx.compiles.install()
    ctx.phases.mark("runtime_start")
    log(f"device: platform={dev.platform} device_kind={dev.device_kind!r} count={len(devs)} "
        f"(cell uses {ctx.chips}); jax {jax.__version__}; compile cache {ctx.cache_stats.dir}")
    log(f"cell: {json.dumps(cell)} seed={ctx.seed} seconds={ctx.seconds} trace={int(ctx.trace)}"
        + (" REHEARSAL (cpu, tiny sizes: no number below is a measurement)" if ctx.rehearsal else ""))
    log("config:", json.dumps({k: v for k, v in config.items() if k != "rehearsal"}))
    log("traffic:", json.dumps({k: v for k, v in traffic.items() if k != "rehearsal"}))

    runner = ctx.load(traffic["kind"] + "_cell.py")
    run = runner.run(ctx)

    device = device_record(jax, run.get("trace_summary"))
    run.update(cell=cell, config=config, traffic=traffic, peaks=peaks, chips=ctx.chips,
               percentile=percentile, load=ctx.load, log=log, device=device)
    run["counters"]["setup.compile_misses"] = ctx.cache_stats.writes
    run["counters"]["compile_requests"] = ctx.cache_stats.requests
    run["counters"]["compile_hits"] = ctx.cache_stats.hits
    log("setup_s split:", json.dumps(ctx.phases.summary()), "total",
        round(run["end_to_end"]["setup_s"], 3))
    log(f"compile cache: requests={ctx.cache_stats.requests} hits={ctx.cache_stats.hits} "
        f"misses={ctx.cache_stats.writes}; compiles inside the window: "
        f"{run['counters']['window.compiles']}")
    if run["counters"]["window.compiles"]:
        run["correct"] = False
        log("NOT CORRECT: something compiled inside the measured window")

    if ctx.trace:
        want = [m for m in bench["per_layer"] if metric_applies(m, cell["name"])]
        values = per_layer_metrics(run)
    else:
        want = [m for m in bench["end_to_end"] if metric_applies(m, cell["name"])]
        values = run["end_to_end"]
    metrics = {}
    for m in want:
        if m["name"] in values:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        else:
            log(f"metric {m['name']} has nothing to read in this run; left out")
    extra = sorted(set(values) - {m["name"] for m in want})
    if extra:
        log("computed but not declared for this cell in BENCHMARK.json:", extra)
    tsum = run.get("trace_summary")
    if ctx.rehearsal:
        print(json.dumps({"rehearsal": True, "correct": bool(run["correct"]),
                          "attempted": run["attempted"], "failed": run["failed"],
                          "would_report": sorted(metrics),
                          "device": {k: device[k] for k in ("platform", "kind", "count")}}))
        return 0 if run["correct"] else 1
    result = {"correct": bool(run["correct"]), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics, "device": device}
    if tsum is not None:
        reduce = ctx.load("reduce.py")
        result["breakdown"] = {"device_ops": reduce.top(tsum["exclusive_s"], 10, "other operations"),
                               "idle_gaps": reduce.top(tsum["gaps_s"], 10, "other spans")}
    compared = run.get("compared")  # what `correct` compared, each number beside its limit
    if compared is not None:
        compared["window_compiles"] = {"value": run["counters"]["window.compiles"], "limit": 0}
        result["compared"] = compared  # last in the line
    print(json.dumps(result), flush=True)
    if compared is not None:  # and as the last lines of standard error
        for name, c in compared.items():
            print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
        print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
