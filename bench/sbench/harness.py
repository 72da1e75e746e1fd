"""One run of one cell: set-up, a measured window, the check, the result.

Everything a cell needs is found by name from `BENCHMARK.json`:

  configs/<file>                  the configuration (sizes, deployment,
                                  limits of the check, its reference)
  reference/<reference>.py        the configuration's plain reference
  traffic/<traffic>.json          the traffic mix, read by `traffic.py`
  metrics/<per-layer name>.py     one reader per per-layer metric:
                                  `read(w) -> float | None`

Set-up (`setup_s`): weights on the device from the configuration's
`weights_seed`, the corpus from the run's seed, the offline phase
(prefill, compression, int8 rung: `build_profiles`), the plan of the
template (a prepared query), and one execution of the plan, which
compiles every flush shape the window drives. The window runs the
prepared plan back to back through `Session.iter_run`, in whole
executions until one ends `seconds` or more after the window opened, and
counts every tuple of every partition that settles in it.
"""
from __future__ import annotations

import contextlib
import gzip
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from sbench import check as CHK
from sbench import flops as FL
from sbench import traffic as TR
from sbench.peaks import peaks_for

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class NoChip(RuntimeError):
    """The accelerator the cell asks for is not there."""


# ---------------------------------------------------------------- lookup

def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str

    def reference(self):
        return load_module(os.path.join(
            self.bench_dir, "reference", self.config["reference"] + ".py"),
            "ref_" + self.config["reference"])

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py"),
                           "metric_" + metric.replace(".", "_")).read


def find_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[name]
    centry = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, chips=int(w["chips"]), config_name=centry["name"],
        config=load_json(os.path.join(root, centry["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(bench_dir, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        bench_dir=bench_dir)


# ---------------------------------------------------------------- device

def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform!r}, not a "
                     f"TPU: no device metrics without the chip")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, {len(devs)} visible")
    return devs


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache: JAX_COMPILATION_CACHE_DIR where set, else a
    fixed directory in the checkout. Every compile is kept, however
    short, so a run finds the small programs of the offline phase too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------- program

def model_config(name: str, c: Dict):
    from repro.configs.base import ModelConfig
    m = FL.dims(c)
    return ModelConfig(
        name=name, family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["KV"], d_head=m["dh"], d_ff=m["ff"],
        vocab_size=m["V"], attn_kind="gqa", rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=c["torch_dtype"])


@contextlib.contextmanager
def env(name: str, value: Optional[str]):
    old = os.environ.get(name)
    if value is not None:
        os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


def annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class LoadSpan:
    """Benchmark-side span around `CacheStore.load_batch`: time inside it,
    and the bytes of the padded batches it returned against the real
    bytes of the tuples they carry."""

    def __init__(self, store, traced: bool):
        self.store, self.inner = store, store.load_batch
        self.traced, self.on = traced, False
        self.secs, self.calls, self.padded, self.real = 0.0, 0, 0, 0
        store.load_batch = self

    def __call__(self, cfg, profile, item_ids, *a, **kw):
        t0 = time.perf_counter()
        with annotate("bench.load_batch", self.traced):
            cache, lengths = self.inner(cfg, profile, item_ids, *a, **kw)
        if self.on:
            self.secs += time.perf_counter() - t0
            self.calls += 1
            self.padded += sum(int(v.nbytes) for k, v in cache.items()
                               if k != "lengths")
            n_real = kw.get("n_real")
            ids = list(item_ids)[:n_real] if n_real is not None else item_ids
            self.real += sum(self.store.item_nbytes(profile, i) for i in ids)
        return cache, lengths


def _log_saves(store, per_doc: int, n_docs: int, clock, t_start, log):
    """Progress of the offline phase: a line every eighth of the corpus."""
    inner, seen = store.save, [0]

    def save(profile, item_id, arrays, length):
        inner(profile, item_id, arrays, length)
        seen[0] += 1
        step = max(1, n_docs // 8) * per_doc
        if seen[0] % step == 0:
            log(f"[setup] t={time.perf_counter() - t_start:.1f}s "
                f"{seen[0] // per_doc} documents profiled, "
                f"compiles={clock.n} compile_s={clock.secs:.1f}")

    store.save = save


def wrap_calls(obj, names, traced: bool, fault: Optional[str] = None):
    """Benchmark-side spans around engine entry points (traced runs), and
    the faults the check must catch (tests only)."""
    for name in names:
        inner = getattr(obj, name)

        def call(*a, _inner=inner, _name=name, **kw):
            with annotate("bench." + _name, traced):
                out = _inner(*a, **kw)
            return FAULTS[fault](_name, out) if fault else out

        setattr(obj, name, call)


def _alter(name, out):
    """An answer altered where it is produced."""
    if name == "run_filter":
        return out + 3.0
    vals, conf = out
    return 8 + (np.asarray(vals) - 8 + 1) % 8, conf


def _half(name, out):
    """Half of each flush left out: its tuples get the mean of the rest."""
    if name == "run_filter":
        out = np.array(out)
        h = max(1, len(out) // 2)
        out[h:] = out[:h].mean()
        return out
    vals, conf = (np.array(x) for x in out)
    h = max(1, len(vals) // 2)
    vals[h:], conf[h:] = vals[0], conf[:h].mean()
    return vals, conf


FAULTS = {"alter": _alter, "half": _half}


# ---------------------------------------------------------------- window

@dataclass
class Window:
    """What the window did; per-layer metric readers read this."""
    seconds: float = 0.0
    tuples: int = 0
    executions: int = 0
    stage_stats: List[Any] = field(default_factory=list)
    scored: Counter = field(default_factory=Counter)   # (op_name, id) -> n
    decisions: Dict[int, Any] = field(default_factory=dict)
    plan_s: Optional[float] = None
    load: Optional[LoadSpan] = None
    cache_hits: int = 0
    cache_misses: int = 0
    trace: Optional[Dict] = None
    config: Dict = field(default_factory=dict)
    peaks: Any = None
    work: Optional[Any] = None          # (op_name, id) -> (ratio, quant,
    #                                     real cache bytes)


def _execute(sess, plan, query, docs, rec, w: Window, timed: bool,
             traced: bool):
    """One whole execution of the prepared plan; with `timed`, counts
    every tuple of every partition as it settles."""
    gen = sess.iter_run(plan, query, docs, backend=rec)
    decisions = {}
    try:
        with annotate("bench.run", traced):
            for part in gen:
                for j in range(part.hi - part.lo):
                    i = docs[part.lo + j].item_id
                    decisions[i] = (bool(part.accepted[j]), {
                        li: int(v[j]) for li, v in part.map_values.items()})
                if timed:
                    w.tuples += len(part)
                    w.stage_stats.extend(part.stage_stats)
    finally:
        gen.close()
    w.decisions = decisions


# ---------------------------------------------------------------- run

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True,
             control: bool = False, fault: Optional[str] = None,
             log=None, compile_cache: bool = True) -> Dict:
    """One run; returns the result object (the last line's content)."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    import jax
    if require_chip:
        devs = require_chips(cell.chips)
    else:
        devs = jax.devices()
    device = devs[0]
    cache_dir = enable_compile_cache() if compile_cache else None
    from sbench.clock import CompileClock
    clock = CompileClock()
    from repro.api import Session, SessionConfig
    from repro.cache.store import CacheStore, Profile
    from repro.serving.engine import ServingEngine

    c, t, dep = cell.config, cell.traffic, cell.config["deployment"]
    refmod = cell.reference()
    mcfg = model_config(cell.config_name, c)
    # the model is the deployment's: its weights come from the
    # configuration's own seed, so that every run serves the same model
    # and the run's seed changes only the documents
    key = jax.random.PRNGKey(int(c["weights_seed"]))
    params = refmod.make_params(c, key)
    docs = TR.make_corpus(t, seed, int(c["vocab_size"]))
    store_root = tempfile.mkdtemp(prefix="stretto_bench_")
    w = Window(config=c)
    try:
        eng = ServingEngine(
            CacheStore(store_root),
            memory_budget_bytes=float(dep["memory_budget_bytes"]),
            max_batch=int(dep["max_batch"]), kernels=dep["kernels"],
            fused=True, device_cache=True, async_h2d=True)
        eng.register_model(cell.config_name, mcfg, params)
        log(f"[setup] t={time.perf_counter() - t_start:.1f}s weights and "
            f"{len(docs)} documents made; offline phase starts")
        _log_saves(eng.store, len(dep["ratios"]) + len(dep["int8_ratios"]),
                   len(docs), clock, t_start,
                   log)
        t0 = time.perf_counter()
        with env("STRETTO_KERNELS", dep.get("offline_scoring_kernels")):
            eng.build_profiles(cell.config_name, docs,
                               ratios=dep["ratios"],
                               prefill_batch=int(dep["prefill_batch"]),
                               quant_ratios=dep["int8_ratios"])
        offline_s = time.perf_counter() - t0
        # what the offline phase wrote: every rung of every document
        store_bytes = sum(
            eng.store.item_nbytes(Profile(cell.config_name, r, q), d.item_id)
            for r, q in [(r, False) for r in dep["ratios"]]
            + [(r, True) for r in dep["int8_ratios"]] for d in docs)
        load = LoadSpan(eng.store, trace)
        wrap_calls(eng, ("run_filter", "run_map"), trace, fault)
        planner = None
        if dep.get("planner"):
            from repro.core.optimizer import PlannerConfig
            planner = PlannerConfig(**dep["planner"])
        scfg = SessionConfig(
            models=(cell.config_name,),
            sm_ratios=tuple(r for r in dep["ratios"] if r > 0),
            lg_ratios=(), sm_int8=tuple(dep["int8_ratios"]),
            include_cheap=False, profile_ratios=tuple(dep["ratios"]),
            partition_size=int(t["partition_size"]),
            dispatcher=dep["dispatcher"], planner=planner)
        sess = Session(scfg, engine=eng)
        frame = TR.frame_for(sess, docs, t)
        query = frame.to_query()
        log(f"[setup] t={time.perf_counter() - t_start:.1f}s offline "
            f"phase {offline_s:.1f}s, compiles={clock.n}; planning")
        t0 = time.perf_counter()
        plan = sess.plan(query, docs)
        first_plan_s = time.perf_counter() - t0
        log(f"[setup] t={time.perf_counter() - t_start:.1f}s planned in "
            f"{first_plan_s:.1f}s, compiles={clock.n}; warm-up execution")
        rec = CHK.Recorder(sess.backend)
        _execute(sess, plan, query, docs, rec, w, False, False)  # warm-up
        if trace:
            # a second, warm plan of the same template, timed alone
            from repro.core.planner import plan_query
            from repro.runtime.dispatch import DEFAULT_COALESCE
            t0 = time.perf_counter()
            plan_query(query, docs, sess.backend, scfg.planner,
                       sample_frac=scfg.sample_frac, seed=scfg.seed,
                       reorder=scfg.reorder, coalesce=DEFAULT_COALESCE)
            w.plan_s = time.perf_counter() - t0
        setup = clock.snapshot()
        setup_s = time.perf_counter() - t_start
        log(f"[setup] setup_s={setup_s:.3f} offline_s={offline_s:.3f} "
            f"plan_s(first)={first_plan_s:.3f} compiles={setup['compiles']} "
            f"compile_s={setup['compile_s']:.3f} "
            f"cache_hits={setup['cache_hits']} cache_dir={cache_dir}")
        log(plan.describe())

        # ------------------------------------------------ window
        h0, m0 = eng.dev_cache_hits, eng.dev_cache_misses
        rec.records.clear()
        rec.on = load.on = True
        log_dir = os.path.join(store_root, "trace")
        prof = contextlib.nullcontext()
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            prof = jax.profiler.trace(log_dir, profiler_options=opts)
        with prof:
            with annotate("bench.window", trace):
                # whole executions, until one ends past `seconds`: the
                # window's tuples and time are those of every execution
                # in it, whatever the phase of the deadline
                t0 = time.perf_counter()
                while w.executions == 0 or \
                        time.perf_counter() - t0 < seconds:
                    _execute(sess, plan, query, docs, rec, w, True, trace)
                    w.executions += 1
                w.seconds = time.perf_counter() - t0
        in_window = clock.snapshot()
        rec.on = load.on = False
        w.cache_hits = eng.dev_cache_hits - h0
        w.cache_misses = eng.dev_cache_misses - m0
        w.scored, w.load = rec.scored, load
        peak = device.memory_stats() or {}
        peak = int(peak.get("peak_bytes_in_use", 0))
        rungs = {op: _rung(sess, query, op) for op, _ in w.scored}
        w.work = {k: (*rungs[k[0]], eng.store.item_nbytes(
            Profile(cell.config_name, *rungs[k[0]]), k[1]))
            for k in w.scored}
        window_compiles = in_window["compiles"] - setup["compiles"]
        log(f"[window] seconds={w.seconds:.3f} tuples={w.tuples} "
            f"executions={w.executions} compiles_in_window="
            f"{window_compiles} dev_cache_hits={w.cache_hits} "
            f"dev_cache_misses={w.cache_misses} peak_bytes={peak} "
            f"loads={load.calls} padded_bytes={load.padded} "
            f"real_bytes={load.real} store_bytes={store_bytes}")

        # ------------------------------------------------ trace
        if trace:
            from sbench import trace as TRC
            events = TRC.flatten(TRC.find_xplane(log_dir))
            w.trace = TRC.reduce_trace(events, TRC.window_of(events))
            dump = os.path.join(ROOT, "results", "traces")
            os.makedirs(dump, exist_ok=True)
            with gzip.open(os.path.join(
                    dump, f"{cell.name}.{seed}.events.json.gz"), "wt") as f:
                json.dump(events, f)
        w.peaks = peaks_for(device.device_kind) if require_chip else None

        # ------------------------------------------------ check
        eng.device_cache_clear()
        eng._placed_params.clear()
        del sess, frame
        checks, extra = _check(cell, refmod, params, docs, plan, query, rec,
                               w, seed, control)
    finally:
        shutil.rmtree(store_root, ignore_errors=True)

    correct = all(v["value"] <= v["limit"] for v in checks.values())
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            val = {"tuples_per_s": w.tuples / w.seconds,
                   "setup_s": setup_s}.get(m["name"])
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            val = cell.reader(m["name"])(w)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": int(w.tuples),
           "failed": int(checks["firm_flips"]["value"]),
           "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = w.trace["busy_s"]
        dev["window_s"] = w.trace["window_s"]
        out["breakdown"] = {"device_ops": w.trace["device_ops"],
                            "idle_gaps": w.trace["idle_gaps"]}
    out["info"] = {"setup": setup, "window_compiles": window_compiles,
                   "cache_hit_share": w.cache_hits / max(
                       1, w.cache_hits + w.cache_misses),
                   "executions": w.executions,
                   "loads": w.load.calls, "padded_bytes": w.load.padded,
                   "real_bytes": w.load.real, "store_bytes": store_bytes,
                   **extra}
    out["checks"] = checks
    return out


def _rung(sess, query, op_name):
    for op in query.semantic_ops:
        try:
            phys = sess.backend.resolve(op, op_name)
        except KeyError:
            continue
        return float(phys.ratio), bool(phys.quant)
    raise KeyError(op_name)


def _check(cell, refmod, params, docs, plan, query, rec, w, seed,
           control: bool):
    """Reference answers for a sample drawn from the seed; the numbers
    compared, each with its limit."""
    t, lim = cell.traffic, cell.config["limits"]
    t0 = time.perf_counter()
    s_ref = -(-int(t["length"]["max"]) // 128) * 128
    rng = TR.rng_for(seed, 2)
    sample = CHK.sample_docs(docs, int(t["check_docs"]), rng)
    scorer = CHK.RefScorer(refmod.Reference(cell.config, "f32"),
                           refmod.keep_count, params, t, docs, s_ref)
    cmp = CHK.Comparison(scorer, t, plan, query, rec.resolve, sample,
                         rec.records)
    got = {k: rec.records[k] for k in cmp.tuples}
    gap = cmp.score_gap(got)
    flips = cmp.firm_flips(w.decisions, lim["score_gap"])
    checks = {"score_gap": {"value": gap, "limit": lim["score_gap"]},
              "firm_flips": {"value": flips, "limit": lim["firm_flips"]}}
    extra = {"checked_tuples": len(cmp.tuples), "checked_docs": len(sample)}
    if control:
        # the control takes the program's place: its answers and the
        # decisions of the plan's cascade replayed on them are what the
        # run's `correct` is decided on; the program's own numbers are
        # kept beside them
        ctl = CHK.RefScorer(refmod.Reference(cell.config, "fp8"),
                            refmod.keep_count, params, t, docs, s_ref)
        extra["program"] = {k: v["value"] for k, v in checks.items()}
        checks = {
            "score_gap": {"value": cmp.score_gap(cmp.answers_of(ctl)),
                          "limit": lim["score_gap"]},
            "firm_flips": {"value": cmp.firm_flips(None, lim["score_gap"],
                                                   ctl),
                           "limit": lim["firm_flips"]}}
    extra["check_s"] = time.perf_counter() - t0
    return checks, extra
