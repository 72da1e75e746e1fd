#!/usr/bin/env python3
"""Bring-up check: Stretto's semantic-query path on a TPU, end to end.

Drives the main path through the entry points a user calls
(`Session` -> planner -> runtime -> `ServingEngine` -> Pallas kernels)
and checks what comes out. One process, no child processes; every corpus,
weight and profile is generated from `--seed`.

    python chip_smoke.py [--seed N]             # one chip: phases a-c
    python chip_smoke.py --chips 4 [--seed N]   # four chips: phase e only

  a. device      fail unless JAX's first device is a TPU
  b. planted     the planted sm/lg models through `Session(SessionConfig)`
                 with kernels left at auto: explain, execute a
                 sem_filter + sem_map query, metrics; the flush programs
                 must call the compiled Pallas kernel (`tpu_custom_call`),
                 and one flush per rung (float and int8) must agree with
                 the jnp reference kernels within LOGODDS_TOL
  c. full width  Llama-3.1-8B at its published widths, bf16, random
                 weights from the seed, depth cut to fit one chip; the
                 same checks through `Session(config, engine=eng)`, plus
                 peak HBM
  e. --chips 4   only the mesh:4 dispatcher against inline on the planted
                 session: decisions equal, every device holding its shard

Each phase prints its wall time and its XLA compile time (persistent-cache
retrievals included; see `repro.launch.compile_cache`). Any failed check
exits non-zero before the last line. The last line of a passing run is
one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.api import Session, SessionConfig  # noqa: E402
from repro.cache.store import CacheStore  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data.synthetic import (TOK_NO, TOK_YES, filter_query_token,  # noqa: E402
                                  make_dataset)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

# Largest |log-odds(Pallas) - log-odds(ref)| accepted in one flush, as a
# fraction of that flush's largest |log-odds(ref)| (at least 1). Both
# kernels accumulate in float32 but round the MXU's float32 operands
# differently (XLA's default precision multiplies in bf16 passes), and
# the model's layers carry that difference to the answer logits: on a
# TPU v5e it is about 1% of the log-odds range for the planted models
# and 1-3% at Llama-3.1-8B widths (most on the int8 rung). Wherever |ref|
# exceeds this fraction of the range, Pallas and ref must also take the
# same decision.
LOGODDS_TOL = 0.05
N_PLANTED_ITEMS = 200
N_FULL_ITEMS = 256
N_MESH_ITEMS = 96                   # 24 per shard on four chips
FULL_DEPTH = 8                      # of Llama-3.1-8B's 32 layers
RATIOS = (0.0, 0.5, 0.8)
INT8_RATIO = 0.5
CHECK_BATCH = 64                    # items in one checked flush


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


class CompileClock:
    """Sums XLA backend compile time (persistent-cache retrievals
    included) from JAX's monitoring events."""

    def __init__(self):
        self.secs, self.n, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.n += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0, n0, h0 = time.perf_counter(), self.secs, self.n, self.hits
        yield
        print(f"[{name}] ok wall_s={time.perf_counter() - t0:.3f} "
              f"compile_s={self.secs - c0:.3f} compiles={self.n - n0} "
              f"cache_hits={self.hits - h0}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_kernel(eng: ServingEngine, model: str, ratio: float, ids,
                   quant: bool) -> None:
    """The flush program of this rung calls the compiled Pallas kernel."""
    text = eng.lower_flush(model, ratio, ids, [filter_query_token(1)],
                           quant=quant).as_text()
    check("tpu_custom_call" in text,
          f"{model} r={ratio} quant={quant}: the flush program has no "
          f"tpu_custom_call, so kernels=auto did not pick compiled Pallas")


def reference_engine(eng: ServingEngine) -> ServingEngine:
    """An engine over the same store and weights whose attention runs the
    jnp reference kernels."""
    ref = ServingEngine(eng.store, memory_budget_bytes=eng.memory_budget,
                        max_batch=eng.max_batch, kernels="ref")
    for name, em in eng.models.items():
        ref.register_model(name, em.cfg, em.params)
    return ref


def compare_rungs(eng: ServingEngine, models, ids) -> float:
    """One flush per rung under auto and under ref; returns the largest
    log-odds difference and fails past LOGODDS_TOL."""
    ref = reference_engine(eng)
    rungs = [(r, False) for r in RATIOS] + [(INT8_RATIO, True)]
    worst = 0.0
    for model in models:
        for ratio, quant in rungs:
            require_kernel(eng, model, ratio, ids, quant)
            args = (model, ratio, ids, [filter_query_token(1)], TOK_YES,
                    TOK_NO)
            got = eng.run_filter(*args, quant=quant)
            want = ref.run_filter(*args, quant=quant)
            check(bool(np.isfinite(got).all()),
                  f"{model} r={ratio} quant={quant}: non-finite log-odds")
            diff = float(np.abs(got - want).max())
            scale = max(1.0, float(np.abs(want).max()))
            firm = np.abs(want) > LOGODDS_TOL * scale
            flips = int(np.sum(np.sign(got[firm]) != np.sign(want[firm])))
            print(f"  {model} r={ratio:.1f}{' int8' if quant else ''}: "
                  f"max|pallas-ref|={diff:.6g} (of range {scale:.4g}: "
                  f"{diff / scale:.6g}), decision flips {flips}/"
                  f"{int(firm.sum())}", flush=True)
            check(diff <= LOGODDS_TOL * scale and flips == 0,
                  f"{model} r={ratio} quant={quant}: Pallas and ref differ "
                  f"by {diff / scale:.4g} of the log-odds range (limit "
                  f"{LOGODDS_TOL}) with {flips} decision flips")
            worst = max(worst, diff)
    return worst


def run_query(sess: Session, items) -> None:
    """explain, execute and metrics of a sem_filter + sem_map query."""
    frame = (sess.frame(items)
             .sem_filter("mentions topic 1", task_id=1)
             .sem_map("extract field 2", task_id=2)
             .with_guarantees(recall=0.75, precision=0.75))
    print(frame.explain(), flush=True)
    res = frame.execute()
    m = res.metrics()
    check(res.accepted.shape == (len(items),), "wrong decision shape")
    check(all(np.isfinite(m[k]) for k in ("precision", "recall")),
          f"non-finite metrics {m}")
    print(f"  {int(res.accepted.sum())}/{len(items)} accepted; vs gold: "
          f"precision={m['precision']:.4f} recall={m['recall']:.4f} "
          f"(targets 0.75)", flush=True)


def phase_device(n_chips: int):
    devs = jax.devices()
    d = devs[0]
    print(f"[a device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d.platform == "tpu",
          f"JAX's first device is {d.platform!r}, not a TPU")
    check(len(devs) >= n_chips,
          f"{n_chips} chips asked for, {len(devs)} visible")
    return d


def planted_session(seed: int, **overrides) -> Session:
    return Session(SessionConfig(
        model_seed=seed, sm_int8=(INT8_RATIO,), lg_int8=(INT8_RATIO,),
        **overrides))


def phase_planted(seed: int) -> float:
    ds = make_dataset("smoke", N_PLANTED_ITEMS, seed=seed)
    with planted_session(seed) as sess:
        run_query(sess, ds.items)
        ids = [it.item_id for it in ds.items[:CHECK_BATCH]]
        return compare_rungs(sess.engine, ("sm", "lg"), ids)


def full_width_config():
    """Llama-3.1-8B at its published widths, depth cut to FULL_DEPTH."""
    base = get_config("stretto-llama-8b")
    return base, dataclasses.replace(
        base, name=f"{base.name}-{FULL_DEPTH}L", n_layers=FULL_DEPTH)


def _tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def phase_full_width(seed: int, device) -> float:
    base, cfg = full_width_config()
    limit = device.memory_stats()["bytes_limit"]
    key = jax.random.PRNGKey(seed)
    full = _tree_bytes(jax.eval_shape(lambda k: init_params(base, k), key))
    print(f"  depth cut {base.n_layers} -> {cfg.n_layers} layers: "
          f"{base.n_layers}-layer bf16 weights {full / 1e9:.3f} GB against "
          f"{limit / 1e9:.3f} GB of HBM", flush=True)
    params = init_params(cfg, key)
    print(f"  {cfg.n_layers}-layer weights {_tree_bytes(params) / 1e9:.3f} "
          f"GB (d_model {cfg.d_model}, heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads}, d_head {cfg.d_head}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size})", flush=True)

    ds = make_dataset("smoke-full", N_FULL_ITEMS, seed=seed)
    root = tempfile.mkdtemp(prefix="stretto_smoke_")
    try:
        eng = ServingEngine(CacheStore(root))
        eng.register_model(cfg.name, cfg, params)
        eng.build_profiles(cfg.name, ds.items, ratios=RATIOS,
                           quant_ratios=(INT8_RATIO,))
        ids = [it.item_id for it in ds.items[:CHECK_BATCH]]
        mem = eng.lower_flush(cfg.name, 0.0, ids, [filter_query_token(1)]
                              ).compile().memory_analysis()
        prog = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        print(f"  flush program ({len(ids)} items, r=0.0): "
              f"{prog / 1e9:.3f} GB (memory_analysis)", flush=True)
        check(prog < limit, "the full-width flush program does not fit")
        config = SessionConfig(models=(cfg.name,), sm_ratios=RATIOS[1:],
                               lg_ratios=(), sm_int8=(INT8_RATIO,),
                               include_cheap=False, profile_ratios=RATIOS)
        with Session(config, engine=eng) as sess:
            run_query(sess, ds.items)
        worst = compare_rungs(eng, (cfg.name,), ids)
        peak = device.memory_stats()["peak_bytes_in_use"]
        print(f"  peak_bytes_in_use={peak} ({peak / 1e9:.3f} GB)",
              flush=True)
        return worst
    finally:
        shutil.rmtree(root, ignore_errors=True)


def phase_mesh(seed: int, n: int) -> None:
    """mesh:n against inline over one plan: equal decisions, and every
    device holding params and caches for its shard."""
    ds = make_dataset("smoke-mesh", N_MESH_ITEMS, seed=seed)
    with planted_session(seed) as sess:
        frame = (sess.frame(ds.items)
                 .sem_filter("mentions topic 1", task_id=1)
                 .sem_map("extract field 2", task_id=2)
                 .with_guarantees(recall=0.75, precision=0.75))
        inline = frame.execute(dispatcher="inline").raw
        mesh = frame.execute(dispatcher=f"mesh:{n}").raw
        check(np.array_equal(inline.accepted, mesh.accepted),
              "mesh and inline accept different items")
        check(set(inline.map_values) == set(mesh.map_values) and all(
            np.array_equal(inline.map_values[k], mesh.map_values[k])
            for k in inline.map_values),
            "mesh and inline extract different values")
        eng = sess.engine
        placed = sorted({dev for _, dev in eng._placed_params})
        want = sorted(d.id for d in jax.devices()[:n])
        print(f"  {int(inline.accepted.sum())}/{len(ds.items)} accepted by "
              f"both; params placed on devices {placed}", flush=True)
        check(placed == want, f"params placed on {placed}, not {want}")
        on_dev = sorted({k[-1] for k in eng._dev_cache} - {None})
        print(f"  device-cached flush batches on devices {on_dev}",
              flush=True)
        check(on_dev == want, f"flush batches cached on {on_dev}, not {want}")
        for d in jax.devices()[:n]:
            used = d.memory_stats()["bytes_in_use"]
            print(f"  device {d.id}: bytes_in_use={used}", flush=True)
            check(used > 0, f"device {d.id} holds nothing")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    try:
        with clock.phase("a device"):
            device = phase_device(args.chips)
        if args.chips > 1:
            with clock.phase(f"e mesh:{args.chips} vs inline"):
                phase_mesh(args.seed, args.chips)
        else:
            with clock.phase("b planted"):
                worst = phase_planted(args.seed)
            print(f"  planted max|pallas-ref| = {worst:.6g}", flush=True)
            with clock.phase("c full width"):
                worst = phase_full_width(args.seed, device)
            print(f"  full-width max|pallas-ref| = {worst:.6g}", flush=True)
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
