"""The system under test, in a process of its own: one deployment of the
C3O hub served by ``repro.serve.edge`` on a localhost port.

Started by ``bench/run.py``, which never imports JAX.  This process holds
the chip.  It builds the hub from the seed, fits every predictor, warms
the lane tick sizes the mix names, starts the edge and then talks to its
parent in JSON lines: events on stdout, commands on stdin.

    parent                      this process
                                {"event": "ready", "port": ..., ...}
    {"cmd": "window_start"}  -> (trace starts, compile count reset)
                                {"event": "window_started"}
    {"cmd": "window_end"}    -> {"event": "window_ended", "compiles": ...}
    {"cmd": "finish"}        -> edge stopped, peak memory and trace read
                                {"event": "finished", ...}; exits

Nothing of the benchmark reaches into the hub's state: the parent sees
only the edge's answers and ``GET /stats``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

T0 = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))


def emit(**event) -> None:
    print(json.dumps(event), flush=True)


class CompileCounter:
    """Counts the executables this process lowers (one per new jit
    signature) and those XLA compiles, from ``jax.monitoring``."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.lowered = self.compiled = 0
        self.lower_s = self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.LOWER:
            self.lowered += 1
            self.lower_s += duration
        elif event == self.COMPILE:
            self.compiled += 1
            self.compile_s += duration

    def reset(self) -> None:
        self.lowered = self.compiled = 0
        self.lower_s = self.compile_s = 0.0

    def read(self) -> dict:
        return {"lowered": self.lowered, "compiled": self.compiled,
                "lower_s": self.lower_s, "compile_s": self.compile_s}


class GcPauses:
    """Garbage-collector pauses of this process, from ``gc.callbacks``."""

    def __init__(self):
        import gc
        self.reset()
        self._t0 = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            d = time.perf_counter() - self._t0
            self.n += 1
            self.total_s += d
            self.max_s = max(self.max_s, d)

    def reset(self) -> None:
        self.n, self.total_s, self.max_s = 0, 0.0, 0.0

    def read(self) -> dict:
        return {"gc_pauses": self.n, "gc_s": self.total_s,
                "gc_max_s": self.max_s}


def build_gateway(cfg: dict, seed: int):
    from repro.core.datastore import RuntimeDataStore
    from repro.core.hub import Hub, JobRepo
    from bench import data
    hub = Hub()
    for job, d in data.job_data(cfg, seed).items():
        hub.publish(JobRepo(job, job, d.schema,
                            RuntimeDataStore(d, seed=seed),
                            model_names=list(cfg["models"]),
                            predictor_kw=dict(
                                pad_rows=cfg["pad_rows"],
                                max_cv_folds=cfg["max_cv_folds"])))
    market = data.price_book(cfg, seed)
    return hub.gateway(data.list_prices(cfg), cfg["scaleouts"], seed=seed,
                       confidence=cfg["confidence"], market=market)


def warm(gw, cfg: dict, mix: dict, seed: int) -> dict:
    """Fit every predictor, then run each tick size the mix names
    (``warm_ticks``) through the same calls the lanes make: a choose tick
    of C contexts and a predict tick of C rows."""
    from bench import data
    t0 = time.perf_counter()
    for job in cfg["jobs"]:
        gw._service(job)                   # fits all its machines
    t1 = time.perf_counter()
    ticks = data.warm_ticks(mix)
    rng = np.random.default_rng([seed, 1])
    for job in cfg["jobs"]:
        repo = gw.hub.get(job)
        svc = gw._service(job)
        for C in ticks["choose"]:
            ctx = data.sample_rows(repo.store.data, C, rng)[:, 1:]
            t_max = np.where(np.arange(C) % 2 == 0, math.nan, 1e3)
            svc.choose_cluster_batch(ctx, t_max)
        for m in repo.store.data.machines:
            pred = repo.predictor_for(m, seed=seed)
            for C in ticks["predict"]:
                pred.predict(data.sample_rows(repo.store.data, C, rng))
    t2 = time.perf_counter()
    return {"fit_s": t1 - t0, "shapes_s": t2 - t1}


async def serve(args, cfg, mix, gw, counter, setup) -> None:
    pauses = GcPauses()
    import jax
    from repro.serve.edge import serve_edge
    edge = cfg["edge"]
    app, server = await serve_edge(gw, "127.0.0.1", 0,
                                   max_batch=edge["max_batch"],
                                   tick_s=edge["tick_s"])
    dev = jax.devices()
    emit(event="ready", port=server.port, setup=setup,
         since_start_s=time.perf_counter() - T0,
         device={"platform": dev[0].platform, "kind": dev[0].device_kind,
                 "count": len(dev)})
    trace_dir = Path(args.trace_dir) if args.trace_dir else None
    window = setup.setdefault("window", {})
    try:
        while True:
            line = await asyncio.to_thread(sys.stdin.readline)
            if not line:
                return
            msg = json.loads(line)
            cmd = msg["cmd"]
            if cmd == "window_start":
                traced = trace_dir is not None and msg.get("trace", True)
                window["traced"] = traced
                if traced:
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0     # host events only
                    opts.enable_hlo_proto = False
                    jax.profiler.start_trace(str(trace_dir),
                                             profiler_options=opts)
                counter.reset()
                pauses.reset()
                window["t0"] = time.perf_counter()
                emit(event="window_started")
            elif cmd == "window_end":
                window["s"] = time.perf_counter() - window["t0"]
                if window["traced"]:
                    jax.profiler.stop_trace()
                emit(event="window_ended", window_s=window["s"],
                     **counter.read(), **pauses.read())
            elif cmd == "finish":
                return
    finally:
        await server.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--trace-dir", default="")
    ap.add_argument("--platform", default="tpu",
                    help="required JAX platform ('any' skips the check)")
    ap.add_argument("--matmul-precision", default="",
                    help="control only: the engine's matmul precision")
    ap.add_argument("--fault", default="",
                    help="fault tests only: a fault of bench/faults.py")
    args = ap.parse_args(argv)
    cfg = json.loads(Path(args.config).read_text())
    mix = json.loads(Path(args.mix).read_text())

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    if args.platform != "any" and (devices[0].platform != args.platform
                                   or len(devices) < args.chips):
        print(f"bench server: needs {args.chips} {args.platform} chip(s), "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    if args.matmul_precision:
        from bench import control
        control.lower_engine_precision(args.matmul_precision)
    if args.fault:
        from bench import faults
        faults.plant(args.fault)
    counter = CompileCounter()
    t0 = time.perf_counter()
    gw = build_gateway(cfg, args.seed)
    setup = {"build_s": time.perf_counter() - t0}
    setup.update(warm(gw, cfg, mix, args.seed))
    setup["compiles"] = counter.read()
    asyncio.run(serve(args, cfg, mix, gw, counter, setup))
    out = {"memory_peak_bytes": max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices[:args.chips])}
    if args.trace_dir:
        from bench import trace_reduce
        out["trace"] = trace_reduce.reduce_dir(
            args.trace_dir, setup["window"].get("s"))
        shutil.rmtree(args.trace_dir, ignore_errors=True)
    emit(event="finished", **out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
