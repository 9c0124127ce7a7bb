"""Run one benchmark cell once on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file, its traffic file and its metrics are
found by name through ``BENCHMARK.json``; the configuration names its
model family (``bench/models/<model>.py``), through which the harness
makes the weights, hands them to the program, checks the program's sizes
and counts the work.  The run makes the weights and the schedule from
``--seed``, warms every program the cell's traffic
uses (set-up), serves a lead-in, then measures ``--seconds`` of open-loop
serving through ``ContinuousBatchingEngine`` on the pallas backend.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the same window and reports its per-layer
metrics.  After the window the served tokens are checked against the
plain float32 reference (``bench/check.py``), and the last line of
standard output is the result as one JSON object.

Off a TPU, with fewer chips than the cell asks for, or on a device that
``bench/peaks.json`` does not list, it prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

NO_CHIP = 3
DRAIN_LIMIT_S = 60.0


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict        # the configuration file
    traffic: Dict       # the traffic file
    end_to_end: List[Dict]
    per_layer: List[Dict]


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    c = cells[workload]
    conf = {x["name"]: x for x in bench["configs"]}[c["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    check_cuts(config)
    family(config["model"])  # a missing family is refused here, early
    with open(os.path.join(root, "bench", "traffic",
                           c["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(workload, int(c["chips"]), config, traffic,
                [m for m in bench["end_to_end"] if applies(m, workload)],
                [m for m in bench["per_layer"] if applies(m, workload)])


def check_cuts(config: Dict) -> None:
    """Refuse a configuration that cuts a key (``reduced``) without giving
    its published value (``published``), gives one for a key it does not
    cut, or does not state the deployment it stands for."""
    cut, published = set(config["reduced"]), set(config.get("published", {}))
    bad = [f"{k}: cut without its published value"
           for k in sorted(cut - published)]
    bad += [f"{k}: a published value, but not in reduced"
            for k in sorted(published - cut)]
    if not config.get("deployment"):
        bad.append("no deployment stated")
    if bad:
        raise SystemExit("configuration's cuts: " + "; ".join(bad))


def family(name: str):
    """The model family module that a configuration names under
    ``"model"`` (``bench/models/__init__.py`` lists what it defines):
    ``bench/models/<name>.py``, or a ``.py`` file named by its path from
    the checkout's root.  Loaded once per process."""
    rel = name if name.endswith(".py") else os.path.join(
        "bench", "models", name + ".py")
    path = os.path.join(ROOT, rel)
    key = "bench_family_" + "".join(c if c.isalnum() else "_" for c in rel)
    if key in sys.modules:
        return sys.modules[key]
    if not os.path.isfile(path):
        raise SystemExit(f"no model family {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def program_config(config: Dict):
    """The program's configuration that serves ``config``, as its family
    builds it (cut keys at their served values)."""
    return family(config["model"]).program_config(config)


def check_program(cfg, config: Dict, model) -> None:
    """Refuse a program configuration ``cfg`` unless it serves exactly
    the sizes that ``config`` states (cut ones as cut), in the stated
    dtype; ``model`` is the configuration's family module."""
    bad = model.differences(cfg, config)
    bad += [f"{k}: program {getattr(cfg, k)} != {config['torch_dtype']}"
            for k in ("dtype", "kv_cache_dtype")
            if getattr(cfg, k) != config["torch_dtype"]]
    if bad:
        raise SystemExit("the program does not serve the configuration: "
                         + "; ".join(bad))


def enable_compile_cache() -> str:
    """JAX's persistent cache in one fixed directory inside the checkout,
    so that two checkouts share nothing and only a checkout's first run
    compiles.  Every program is cached, the sub-second eager ones too."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs compiled or loaded from the persistent cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax.monitoring as mon
        self.counts: Counter = Counter()
        mon.register_event_duration_secs_listener(
            lambda name, *a, **k: self._seen(name))
        mon.register_event_listener(lambda name, *a, **k: self._seen(name))

    def _seen(self, name: str) -> None:
        if name in self.EVENTS:
            self.counts[name] += 1

    def total(self) -> int:
        return sum(self.counts.values())


def engine_misses() -> int:
    from repro.core import engine
    return sum(v for fam in engine.stats().values() for k, v in fam.items()
               if k.startswith(("plan_misses", "kernel_misses")))


@dataclasses.dataclass
class Reading:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""
    dims: Dict
    model: object                 # the configuration's family module
    peaks: Dict
    window_s: float
    work: object                  # bench.serve.WindowWork
    phase_s: Dict[str, float]     # scheduler phase seconds in the window
    trace: object                 # bench.trace.TraceSummary, or None
    memory_peak_bytes: Optional[int]


def read_metric(name: str, reading: Reading) -> Optional[float]:
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(reading)


def end_to_end(name: str, run: Dict) -> float:
    from bench import stats
    if name == "setup_s":
        return run["setup_s"]
    if name == "ttft_p90_ms":
        return 1e3 * stats.percentile(run["ttft"], 90)
    if name == "itl_p95_ms":
        return 1e3 * stats.percentile(run["itl"], 95)
    if name == "output_tokens_per_s":
        return stats.rate(run["tokens"], run["t_open"], run["t_close"])
    raise KeyError(f"no arithmetic for end-to-end metric {name!r}")


def run_cell(cell: Cell, cfg, seed: int, seconds: float, trace: bool, *,
             peaks: Optional[Dict] = None, t_start: float = T_START,
             tamper: Optional[Callable] = None,
             inspect: Optional[Callable] = None, say=print) -> Dict:
    """One run of ``cell`` on the program configuration ``cfg``; returns
    the result object.  ``tamper(engine)`` (tests) breaks the system under
    test before it serves; ``inspect(ref, weights, dims, finished, rids,
    n_at)`` (the control) reads the checked requests after the check."""
    import jax
    import numpy as np

    from repro.core import use
    from repro.launch.serve import load_params
    from repro.models.attention import PageSpec
    from repro.runtime.batching import ContinuousBatchingEngine

    from bench import check, load, serve, stats, weights
    from bench import trace as trace_lib

    config, traffic = cell.config, cell.traffic
    model = family(config["model"])
    check_program(cfg, config, model)
    dims = model.dims(config)
    geo = config["serving"]
    compiles = CompileCounter()

    t0 = time.time()
    w = jax.block_until_ready(model.make(dims, seed))
    params = model.to_program(w, dims)
    weights.check_layout(params, jax.eval_shape(lambda: load_params(cfg)))
    t_weights = time.time() - t0
    plan = load.build(traffic, seed, seconds, dims["vocab_size"])
    say(f"[cell] {cell.name} seed={seed} seconds={seconds} "
        + load.describe(plan))

    with use(backend="pallas"):
        eng = ContinuousBatchingEngine(
            cfg, params, num_slots=geo["slots"],
            spec=PageSpec(geo["pages"], geo["page_size"],
                          -(-geo["max_context"] // geo["page_size"])))
        if tamper is not None:
            tamper(eng)
        t0 = time.time()
        serve.warm(eng, load.ladder(traffic["prompt_tokens"]),
                   dims["vocab_size"])
        t_warm = time.time() - t0

        lead = float(traffic.get("lead_in_s", 0.0))
        t_open = time.perf_counter() + lead
        drv = serve.OpenLoop(eng, plan, t_open)
        drv.serve_until(t_open, record=False)
        setup_s = time.time() - t_start

        log_dir = tempfile.TemporaryDirectory() if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(log_dir.name, profiler_options=opts)
        c0, m0, ev0, ph0 = (compiles.total(), engine_misses(),
                            eng.evictions, dict(eng.phase_seconds))
        t_open = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            t_close = drv.serve_until(t_open + seconds, record=True)
        compiles_in, misses_in = (compiles.total() - c0,
                                  engine_misses() - m0)
        queued = len(eng.queue)
        evictions_in = eng.evictions - ev0
        phase = {k: eng.phase_seconds[k] - ph0[k] for k in ph0}
        summary = None
        if trace:
            jax.block_until_ready(eng.cache)
            jax.profiler.stop_trace()
            summary = trace_lib.reduce(trace_lib.find_xplane(log_dir.name))
            log_dir.cleanup()
        drv.serve_until(time.perf_counter() + DRAIN_LIMIT_S, record=False,
                        stop=lambda: not drv.first_token_due())
        memory = serve.memory_peak_bytes()

    # -- end-to-end samples -------------------------------------------------
    due = {t.plan.rid: t.due_at for t in drv.todo if t.plan.in_window}
    emits = {rid: t.emits for rid, t in drv.tracked.items()}
    first = {rid: e[0] for rid, e in emits.items() if e}
    run = dict(setup_s=setup_s, t_open=t_open, t_close=t_close,
               ttft=stats.ttft_samples(due, first),
               itl=stats.itl_samples(emits, t_open, t_close),
               tokens=stats.tokens_in(emits, t_open, t_close))
    late = sorted(drv.lateness()) or [0.0]
    by_third = [[first[r] - t for r, t in due.items() if r in first
                 and k * seconds / 3 <= t - drv.t_open < (k + 1) * seconds / 3]
                for k in range(3)]
    work = drv.work
    live = [len(c) for c in work.decode_contexts] or [0]
    say(f"[setup] weights_s={t_weights} warm_s={t_warm} lead_in_s={lead} "
        f"setup_s={setup_s}")
    say(f"[window] seconds={t_close - t_open} steps={work.steps} "
        f"decode_steps={len(work.decode_contexts)} "
        f"admissions={len(work.prefills)} evictions={evictions_in} "
        f"mean_live_slots={float(np.mean(live))} "
        f"plan_and_kernel_misses={misses_in} jax_compiles={compiles_in} "
        f"output_tokens={run['tokens']} ttft_samples={len(run['ttft'])} "
        f"itl_samples={len(run['itl'])} queued_at_close={queued} "
        "ttft_median_ms_by_third=" + ",".join(
            f"{1e3 * stats.percentile(x, 50):.1f}" if x else "-"
            for x in by_third))
    say(f"[generator] submitted={len(late)} late_p50_ms="
        f"{1e3 * stats.percentile(late, 50)} late_p95_ms="
        f"{1e3 * stats.percentile(late, 95)} late_max_ms={1e3 * late[-1]}")
    say(f"[memory] peak_bytes={memory}")
    attempted = (len(due) if due else
                 len([r for r, e in emits.items()
                      if any(t_open <= x <= t_close for x in e)]))
    failed = len(drv.first_token_due())

    # -- the output check, after the program's state is freed ----------------
    finished = {rid: v for rid, v in drv.finished().items()
                if any(x >= t_open for x in emits[rid])}
    del eng, drv, params
    gc.collect()
    ref = check.reference_module(config["reference"])
    rids = check.sample(finished, seed, int(traffic["check_served_tokens"]))
    t0 = time.time()
    n_at = int(traffic["answer_tokens"]["max"])
    # Nothing finished to check reads as no value, and is not correct.
    gap, n_tok, n_exact = (check.widest_gap(ref, w, dims, finished, rids,
                                            n_at)
                           if rids else (None, 0, 0))
    limit = float(config["check"]["served_logit_gap"])
    checks = {"served_logit_gap": {"value": gap, "limit": limit}}
    correct = gap is not None and check.judge(
        {k: (v["value"], v["limit"]) for k, v in checks.items()})
    if inspect is not None:
        inspect(ref, w, dims, finished, rids, n_at)

    # -- metrics ---------------------------------------------------------------
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": end_to_end(m["name"], run),
                                  "unit": m["unit"]}
    else:
        reading = Reading(dims=dims, model=model, peaks=peaks,
                          window_s=t_close - t_open, work=work,
                          phase_s=phase, trace=summary,
                          memory_peak_bytes=memory)
        for m in cell.per_layer:
            v = read_metric(m["name"], reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    devs = jax.devices()[:cell.chips]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    result["checks"] = checks
    for k, v in checks.items():
        print(f"[check] {k}={v['value']} limit={v['limit']} "
              f"requests={len(rids)} tokens={n_tok} exact={n_exact} "
              f"reference_s={time.time() - t0} correct={correct}",
              file=sys.stderr, flush=True)
    return result


def require_chip(chips: int):
    """The device table entry, or a reason why this machine cannot run."""
    import jax
    from bench import work
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return None, f"no TPU: JAX found {devs[0].platform}"
    if len(devs) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devs)}"
    try:
        return work.load_peaks(devs[0].device_kind), None
    except KeyError as e:
        return None, str(e)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    enable_compile_cache()
    peaks, why = require_chip(cell.chips)
    if peaks is None:
        print(f"[device] {why}; this benchmark measures the chip only",
              file=sys.stderr)
        return NO_CHIP
    cfg = program_config(cell.config)
    result = run_cell(cell, cfg, args.seed, args.seconds, bool(args.trace),
                      peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
