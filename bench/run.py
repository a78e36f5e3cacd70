"""Benchmark of the C3O hub on the chip: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (a hub deployment,
``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``).  One run:

1. starts ``bench/server.py``, which holds the chip, builds the hub from
   the seed, fits it, warms the lane tick sizes the mix names and serves
   it with ``repro.serve.edge``;
2. drives the edge over localhost HTTP/1.1 for ``--seconds`` with the
   mix's generator (``bench/traffic/<generator>.py``, through
   ``bench/loadgen.py``), from this process, which never touches a JAX
   backend;
3. holds every answer against the float64 reference
   (``bench/check.py``) once the window has closed and the server has
   exited;
4. prints the cell's metrics, each read by ``bench/metrics/<name>.py``:
   the end-to-end ones with ``--trace 0``, the per-layer ones with
   ``--trace 1`` (the server then records a profiler trace of the
   window).

The last stdout line is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` when traced, and
``checks`` last); the last stderr lines are each compared number beside
its limit.  Without a TPU the server exits first and this prints no
result and exits non-zero.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: the server's start, fits and first compiles in a fresh checkout
READY_TIMEOUT_S = 1100.0
#: what a metric reads when a request it covers failed
OVER = 1e12


class RunError(Exception):
    """The run cannot give a result; the message says why."""


def load_cell(name: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, its files and the
    metrics it reports."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise RunError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {', '.join(sorted(cells))})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]

    def applies(metric):
        return name in metric.get("workloads", [name])

    return SimpleNamespace(
        name=name, chips=int(cell["chips"]),
        config_file=root / cfg_entry["file"],
        mix_file=root / "bench" / "traffic" / f"{cell['traffic']}.json",
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


class Server:
    """The ``bench/server.py`` child and its JSON-line protocol."""

    def __init__(self, cell, seed: int, trace: bool, platform: str,
                 precision: str, env: dict, fault: str = ""):
        cmd = [sys.executable, str(BENCH / "server.py"),
               "--config", str(cell.config_file), "--mix", str(cell.mix_file),
               "--seed", str(seed), "--chips", str(cell.chips),
               "--platform", platform]
        if trace:
            cmd += ["--trace-dir", str(BENCH / ".trace")]
        if precision:
            cmd += ["--matmul-precision", precision]
        if fault:
            cmd += ["--fault", fault]
        self.proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, env=env,
                                     text=True, bufsize=1)
        self.lines: queue.Queue = queue.Queue()
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line)
        self.lines.put(None)

    def read(self, want: str, timeout: float) -> dict:
        end = time.monotonic() + timeout
        while True:
            try:
                line = self.lines.get(timeout=max(end - time.monotonic(),
                                                  1e-3))
            except queue.Empty:
                raise RunError(f"server gave no {want!r} within "
                               f"{timeout:.0f} s") from None
            if line is None:
                self.lines.put(None)
                raise RunError(f"server exited (code {self.proc.wait()}) "
                               f"before {want!r}")
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            if isinstance(ev, dict) and ev.get("event") == want:
                return ev

    def send(self, cmd: str, **kw) -> None:
        self.proc.stdin.write(json.dumps({"cmd": cmd, **kw}) + "\n")
        self.proc.stdin.flush()

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
                self.proc.wait(timeout=60)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


def child_env(base: dict, platform: str = "tpu") -> dict:
    """The server's environment: the persistent compile cache at a fixed
    directory of the checkout, one per platform the run asks for (a cache
    another backend wrote makes every write of this one fail)."""
    env = dict(base)
    cache = ROOT / ".jax_cache" / platform
    cache.mkdir(parents=True, exist_ok=True)
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    # small executables are cached too: every one is lowered per process
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["PYTHONUNBUFFERED"] = "1"
    return env


async def _stats(port: int):
    from bench.loadgen import http
    from repro.api import codec
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        _, payload = await http(reader, writer, "GET", "/stats")
    finally:
        writer.close()
    return codec.decode(payload.decode("utf-8")).result


async def _selected(port: int, traffic) -> dict:
    """(job, machine) -> the model the hub serves, asked through
    ``/v1/predict`` once the window has closed."""
    from bench.loadgen import http, predict_req
    from repro.api import codec
    out = {}
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        for job in traffic.jobs:
            d = traffic.stores[job]
            for m in d.machines:
                req = predict_req(job, m, d.X[0], 0.0)
                _, payload = await http(reader, writer, "POST",
                                        "/v1/predict", req.body)
                resp = codec.decode(payload.decode("utf-8"))
                if resp.ok:
                    out[(job, m)] = resp.result.selected_model
    finally:
        writer.close()
    return out


async def drive(server: Server, port: int, traffic, trace: bool) -> dict:
    async def on_open():
        server.send("window_start", trace=trace)
        await asyncio.to_thread(server.read, "window_started", 120.0)

    ended = {}

    async def on_close():
        server.send("window_end")
        ended.update(await asyncio.to_thread(server.read, "window_ended",
                                             240.0))

    from bench import host
    before = await _stats(port)
    # the generator's own pauses would read as the server's latency
    gc.collect()
    gc.freeze()
    gc.disable()
    host0 = host.snapshot()
    try:
        late = await traffic.run(port, on_open, on_close)
    finally:
        host1 = host.snapshot()
        gc.enable()
        gc.unfreeze()
    after = await _stats(port)
    selected = await _selected(port, traffic)
    return {"late": late, "before": before, "after": after,
            "window": ended, "selected": selected,
            "host": host.delta(host0, host1)}


def read_metric(name: str, ctx) -> float:
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


def main(argv=None, **kw) -> int:
    """One run.  The keywords are for the benchmark's own tests:
    ``platform="any"`` skips the look for a chip, ``precision`` runs the
    lower-precision control, ``fault`` plants a fault of
    ``bench/faults.py``, ``root`` holds another BENCHMARK.json.  The
    caller's ``JAX_PLATFORMS`` is back as it was on return, so the next
    run's server looks for the chip again."""
    saved = os.environ.get("JAX_PLATFORMS")
    try:
        return _main(argv, **kw)
    finally:
        if saved is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = saved


def _main(argv=None, *, platform: str = "tpu", precision: str = "",
          fault: str = "", root: Path = ROOT, out=sys.stdout,
          err=sys.stderr) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    env = child_env(os.environ, platform)
    # this process only encodes requests and checks answers: keep any
    # JAX it imports off the chip, which belongs to the server
    os.environ["JAX_PLATFORMS"] = "cpu"
    server = None
    try:
        cell = load_cell(args.workload, Path(root))
        cfg = json.loads(cell.config_file.read_text())
        mix = json.loads(cell.mix_file.read_text())
        server = Server(cell, args.seed, bool(args.trace), platform,
                        precision, env, fault)
        from bench import loadgen
        traffic = loadgen.load(cfg, mix, args.seed, args.seconds)
        ready = server.read("ready", READY_TIMEOUT_S)
        dev = ready["device"]
        if platform != "any" and (dev["platform"] != platform
                                  or dev["count"] < cell.chips):
            raise RunError(f"server runs on {dev}, the cell needs "
                           f"{cell.chips} {platform} chip(s)")
        run = asyncio.run(drive(server, ready["port"], traffic,
                                bool(args.trace)))
        server.send("finish")
        fin = server.read("finished", 600.0)
        server.close()
        if server.proc.returncode:
            raise RunError(f"server exited with {server.proc.returncode}")
    except RunError as e:
        print(f"bench: FAIL: {e}", file=err)
        return 2
    finally:
        if server is not None:
            server.close()
    setup_s = traffic.opened - T0
    traffic.decode()
    print(f"bench: generator late p50 {run['late']['late_p50_ms']!r} ms, "
          f"p99 {run['late']['late_p99_ms']!r} ms, max "
          f"{run['late']['late_max_ms']!r} ms, over 50 ms "
          f"{run['late']['late_over_50ms']}; server setup "
          f"{json.dumps(ready['setup'])}; window compiles "
          f"{json.dumps(run['window'])}", file=out)
    print(f"bench: host in the window {json.dumps(run['host'])}", file=out)
    from bench.metrics import latencies, percentile
    for op in ("choose", "predict"):
        lat = latencies(SimpleNamespace(traffic=traffic), (op,))
        if lat:
            print(f"bench: {op} latency ms over {len(lat)} requests: "
                  + ", ".join(f"p{p} {1e3 * percentile(lat, p)!r}"
                              for p in (50, 90, 95, 99)), file=out)
    slow = sorted(traffic.all(), key=lambda r: -(r.done - r.due
                                                  if r.ok else math.inf))
    print("bench: slowest answers (due s, latency ms): " + ", ".join(
        f"({r.due:.3f}, {1e3 * (r.done - r.due):.1f})" for r in slow[:8]),
        file=out)
    late = sorted(traffic.all(), key=lambda r: -(r.sent - r.due))
    print("bench: latest sends (due s, late ms): " + ", ".join(
        f"({r.due:.3f}, {1e3 * (r.sent - r.due):.1f})" for r in late[:8]),
        file=out)
    from bench.check import Checker
    t_check = time.perf_counter()
    checker = Checker(cfg, args.seed, traffic, run["selected"])
    checks = checker.run()
    print(f"bench: reference check took "
          f"{time.perf_counter() - t_check!r} s", file=out)
    for k, what in checker.worst.items():
        print(f"bench: largest {k}: {what}", file=out)
    for k, v in checker.values.items():
        v = np.sort(v)
        print(f"bench: {k} over {len(v)} answers: median "
              f"{float(v[len(v) // 2])!r} p90 "
              f"{float(v[int(0.9 * (len(v) - 1))])!r} max {float(v[-1])!r}",
              file=out)
    ctx = SimpleNamespace(cell=cell, cfg=cfg, mix=mix, traffic=traffic,
                          seconds=args.seconds, setup_s=setup_s,
                          stats_before=run["before"],
                          stats_after=run["after"], window=run["window"],
                          selected=run["selected"], trace=fin.get("trace"),
                          ready=ready)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = read_metric(m["name"], ctx)
        if v is not None:
            # a tail over a request that never came is over any limit
            metrics[m["name"]] = {"value": v if math.isfinite(v)
                                  else OVER, "unit": m["unit"]}
    correct = all(v <= lim for v, lim in checks.values())
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": fin["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": len(traffic.all()),
            "failed": int(checks["failed"][0]), "metrics": metrics,
            "device": device}
    if args.trace and fin.get("trace"):
        tr = fin["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = {k: {"value": v if math.isfinite(v) else OVER,
                          "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}"
              f"{'' if v <= lim else '  OVER'}", file=err)
    err.flush()
    print(json.dumps(line), file=out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
