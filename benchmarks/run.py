"""Benchmark harness: one function per paper table/figure + framework perf.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time per
inner evaluation where meaningful; derived = headline metric).

  engine        fused prediction engine: fit throughput (cold vs warm
                executable cache), candidate-grid scoring predictions/sec on
                a 3-machine x 7-scale-out x 256-context grid, and speedup
                over the seed per-row/fresh-jit path
  serve         configuration service: joint choose_cluster_batch
                throughput and async micro-batched front-end requests/s
  gateway       Hub Gateway API v1: single-job choose requests/s through
                the per-job batch lanes vs the legacy front-end (target
                >= 1x: the redesign may not regress the hot path), plus
                multi-job mixed-operation requests/s and mean per-lane
                batch size
  edge          socket-level serving edge: closed-loop load test (64
                keep-alive connections over a real localhost socket)
                against the in-process gateway on the SAME seeded request
                stream — requests/s, p50/p95/p99, realized predict-lane
                mean batch, and byte-identical-response parity; the
                >=0.5x-of-in-process throughput, mean-batch>1, and parity
                checks are hard SystemExit gates
  ingest        contribution ingestion at 10k stored rows: contributions/s
                and rows/s, cold vs warm, vs the pre-refactor
                re-encode/re-hash/refit-from-scratch path
  compact       store lifecycle: one coverage-aware compaction of a 10k-row
                store — rows retained (>=4x reduction), warm refit speedup
                (>=2x), held-out MAPE delta (<=1pp); all three are hard
                SystemExit gates
  eval          collaborative replay plane smoke: leave-one-user-out mini
                replay wall-clock + per-job accuracy/monotonicity summary
  trust         trust plane smoke: twin-arm adversarial replay (reputation
                weighting off vs on) + gateway token-auth overhead on the
                predict hot path (target <= 5%)
  transfer      cold-start cross-job transfer: nearest-donor lookup cost
                (cold sketch vs version-keyed cache hits; flat re-sketch
                counters are a hard gate) and borrowed-model MAPE on a
                zero-history twin job vs the global-mean baseline (must
                beat it; hard gate)
  table1        dataset structure vs paper Table I
  table2        MAPE local/global x 5 jobs x {ernest,gbm,bom,ogb,c3o} (§VI-C.a)
  fig5          MAPE vs training-set size (§VI-C.b)
  configurator  deadline satisfaction + cost vs overprovisioning (§IV)
  autoconfig    C3O-for-TPU mesh selection quality (beyond-paper)
  kernels       Pallas kernel wall times (interpret) vs jitted jnp oracles
  roofline      per-cell roofline table from experiments/dryrun_*.json

Usage: PYTHONPATH=src python -m benchmarks.run [--splits N] [--only NAME]
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_engine(args):
    import jax

    from repro.core import engine
    from repro.core.configurator import Configurator
    from repro.core.predictor import C3OPredictor
    from repro.workloads import spark_emul as W

    prices = {m.name: m.price for m in W.MACHINES.values()}
    machines = sorted(W.MACHINES)[:3]
    scaleouts = [2, 3, 4, 6, 8, 12, 16]
    rng = np.random.default_rng(0)
    contexts = np.stack([rng.uniform(10, 20, 256),
                         rng.choice([.002, .02, .08], 256)], axis=1)
    data = {m: W.generate_job_data("grep").filter_machine(m)
            for m in machines}

    # --- fit throughput: cold (trace+compile) vs warm executable cache ----
    t0 = time.time()
    preds = {m: C3OPredictor(max_cv_folds=25).fit(d.X, d.y)
             for m, d in data.items()}
    cold = (time.time() - t0) / len(machines)
    t0 = time.time()
    preds = {m: C3OPredictor(max_cv_folds=25).fit(d.X, d.y)
             for m, d in data.items()}
    warm = (time.time() - t0) / len(machines)
    _row("engine.fit_cold", cold * 1e6, "fit+select, per machine type")
    _row("engine.fit_warm", warm * 1e6,
         f"cached executables, speedup={cold / max(warm, 1e-9):.1f}x")

    # --- warm candidate-grid scoring: machines x scale-outs x contexts ----
    n_cand = len(machines) * len(scaleouts) * len(contexts)
    engine.machine_grid_costs(preds, prices, scaleouts, contexts)  # warm-up
    reps = 5
    t0 = time.time()
    for _ in range(reps):
        names, t, cost = engine.machine_grid_costs(preds, prices, scaleouts,
                                                   contexts)
    grid_s = (time.time() - t0) / reps
    _row("engine.grid_score", grid_s / n_cand * 1e6,
         f"candidates/s={n_cand / grid_s:.0f} grid={len(machines)}x"
         f"{len(scaleouts)}x{len(contexts)}")

    # --- choose_batch serving throughput -----------------------------------
    conf = Configurator(preds[machines[0]], machines[0], prices, scaleouts)
    conf.choose_batch(contexts, t_max=400.0)                       # warm-up
    t0 = time.time()
    for _ in range(reps):
        conf.choose_batch(contexts, t_max=400.0)
    batch_s = (time.time() - t0) / reps
    _row("engine.choose_batch", batch_s / len(contexts) * 1e6,
         f"choices/s={len(contexts) / batch_s:.0f}")

    # --- seed per-row path: fresh jax.jit per predict call, one context at
    # a time (the pre-engine FittedModel behavior), measured on a subset ---
    fm = preds[machines[0]]._fitted
    n_sub = 8
    t0 = time.time()
    for ctx in contexts[:n_sub]:
        rows = np.stack([np.concatenate([[s], ctx]) for s in scaleouts])
        import jax.numpy as jnp
        np.asarray(jax.jit(fm.spec.predict)(
            fm.params, jnp.asarray(rows, jnp.float32), fm.aux))
    naive_per_ctx = (time.time() - t0) / n_sub
    warm_per_ctx = batch_s / len(contexts)
    _row("engine.seed_per_row_path", naive_per_ctx * 1e6,
         f"speedup_warm_vs_seed={naive_per_ctx / max(warm_per_ctx, 1e-12):.1f}x"
         " (target >=5x)")


def bench_serve(args):
    import asyncio

    from repro.core.predictor import C3OPredictor
    from repro.core.service import ConfigurationService
    from repro.serve.config_service import AsyncConfigService
    from repro.workloads import spark_emul as W

    prices = {m.name: m.price for m in W.MACHINES.values()}
    machines = sorted(W.MACHINES)
    scaleouts = [2, 3, 4, 6, 8, 12, 16]
    rng = np.random.default_rng(0)
    contexts = np.stack([rng.uniform(10, 20, 256),
                         rng.choice([.002, .02, .08], 256)], axis=1)
    preds = {}
    for m in machines:
        d = W.generate_job_data("grep").filter_machine(m)
        preds[m] = C3OPredictor(max_cv_folds=20).fit(d.X, d.y)
    svc = ConfigurationService(preds, prices, scaleouts)

    # --- synchronous joint grid selection ---------------------------------
    svc.choose_cluster_batch(contexts, t_max=400.0)                # warm-up
    reps = 5
    t0 = time.time()
    for _ in range(reps):
        svc.choose_cluster_batch(contexts, t_max=400.0)
    joint_s = (time.time() - t0) / reps
    n_cand = len(machines) * len(scaleouts) * len(contexts)
    _row("serve.choose_cluster_batch", joint_s / len(contexts) * 1e6,
         f"choices/s={len(contexts) / joint_s:.0f} "
         f"grid={len(machines)}x{len(scaleouts)}x{len(contexts)} "
         f"candidates/s={n_cand / joint_s:.0f}")

    # --- async micro-batched front-end ------------------------------------
    n_req = 512
    t_maxes = [None if i % 4 == 0 else float(rng.uniform(200, 600))
               for i in range(n_req)]

    async def drive():
        async with AsyncConfigService(svc, max_batch=128) as front:
            await asyncio.gather(*[
                front.choose(contexts[i % len(contexts)], t_max=t_maxes[i])
                for i in range(n_req)])
            return front.stats

    asyncio.run(drive())                                           # warm-up
    t0 = time.time()
    stats = asyncio.run(drive())
    serve_s = time.time() - t0
    _row("serve.async_frontend", serve_s / n_req * 1e6,
         f"requests/s={n_req / serve_s:.0f} "
         f"mean_batch={stats.mean_batch:.1f} batches={stats.batches}")


def bench_gateway(args):
    """Hub Gateway API v1 serving throughput.

    ``gateway.single_job``  512 typed choose requests for ONE job through
                            the gateway's batch lane vs the same workload
                            through the legacy ``AsyncConfigService``
                            front-end — the redesign's hot-path guard
                            (target: speedup_vs_legacy >= 1x).
    ``gateway.multi_job``   mixed multi-job stream (choose across jobs +
                            predict/search/contribute riding along):
                            requests/s and realized mean per-lane batch.
    """
    import asyncio

    from repro.api import (AsyncHubGateway, ChooseRequest, ContributeRequest,
                           HubGateway, PredictRequest, SearchRequest)
    from repro.core.datastore import RuntimeDataStore
    from repro.core.hub import Hub, JobRepo
    from repro.core.service import ConfigurationService
    from repro.serve.config_service import AsyncConfigService
    from repro.workloads import spark_emul as W

    prices = {m.name: m.price for m in W.MACHINES.values()}
    scaleouts = [2, 3, 4, 6, 8, 12, 16]
    jobs = ("grep", "sort")

    def make_hub(**predictor_kw):
        hub = Hub()
        for job in jobs:
            d = W.generate_job_data(job)
            hub.publish(JobRepo(job, job, d.schema,
                                RuntimeDataStore(d, seed=0),
                                predictor_kw=dict(predictor_kw)))
        return hub

    # single-job hot path: predictors constructed exactly like the serve
    # lane's (same fold cap, no padding), so gateway-vs-legacy isolates
    # the gateway layer itself
    hub = make_hub(max_cv_folds=20)
    gw = HubGateway(hub, prices, scaleouts)
    # mixed stream: pad_rows, because accepted contributions grow the
    # store and bucketed refits keep the service rebuild hitting cached
    # executables instead of retracing per exact store size
    hub_mixed = make_hub(pad_rows=True, max_cv_folds=15)
    gw_mixed = HubGateway(hub_mixed, prices, scaleouts)
    rng = np.random.default_rng(0)
    n_req = 512
    ctx_grep = [(float(rng.uniform(10, 20)),
                 float(rng.choice([.002, .02, .08]))) for _ in range(n_req)]
    t_maxes = [math.nan if i % 4 == 0 else float(rng.uniform(200, 600))
               for i in range(n_req)]

    # --- legacy single-service front-end on the same workload -------------
    svc = ConfigurationService.from_repo(hub.get("grep"), None, prices,
                                         scaleouts)
    legacy_ctxs = [np.asarray(c) for c in ctx_grep]

    async def drive_legacy():
        async with AsyncConfigService(svc, max_batch=128) as front:
            await asyncio.gather(*[
                front.choose(legacy_ctxs[i], t_max=t_maxes[i])
                for i in range(n_req)])

    # --- gateway lane, single job (typed requests pre-built: the lane is
    # being measured, not the client's envelope construction) --------------
    single_reqs = [ChooseRequest("grep", ctx_grep[i], t_max=t_maxes[i])
                   for i in range(n_req)]
    stats = {}

    async def drive_single():
        async with AsyncHubGateway(gw, max_batch=128) as agw:
            out = await asyncio.gather(*[agw.choose(q) for q in single_reqs])
            assert all(r.ok for r in out)
            stats.update(agw.lane_stats)

    # interleaved best-of-reps: machine drift (CI neighbors, GC) hits both
    # paths alike instead of whichever happened to run second
    legacy_s = single_s = math.inf
    asyncio.run(drive_legacy())                                    # warm-up
    asyncio.run(drive_single())
    for _ in range(5):
        t0 = time.time()
        asyncio.run(drive_legacy())
        legacy_s = min(legacy_s, time.time() - t0)
        t0 = time.time()
        asyncio.run(drive_single())
        single_s = min(single_s, time.time() - t0)
    _row("gateway.single_job", single_s / n_req * 1e6,
         f"requests/s={n_req / single_s:.0f} "
         f"mean_batch={stats['grep'].mean_batch:.1f} "
         f"legacy_rps={n_req / legacy_s:.0f} "
         f"speedup_vs_legacy={legacy_s / single_s:.2f}x (target >=1x)")

    # --- mixed multi-job stream -------------------------------------------
    grep_store = hub_mixed.get("grep").store.data
    sub = grep_store.subset(np.arange(4))
    mixed = []
    for i in range(n_req):
        k = i % 8
        if k == 5:
            mixed.append(PredictRequest("grep", "m5.xlarge",
                                        ((4.0,) + ctx_grep[i],)))
        elif k == 6:
            mixed.append(SearchRequest(""))
        elif k == 7 and i % 128 == 127:
            # an accepted contribution bumps the store version and forces
            # a service rebuild (refit at the grown size) on the next
            # choose tick — rare relative to reads, like hub traffic
            mixed.append(ContributeRequest(
                "grep", tuple(sub.machine_type),
                tuple(map(tuple, sub.X)), tuple(sub.y),
                contributor_id=f"bench{i % 3}"))
        elif k % 2:
            mixed.append(ChooseRequest("sort", ctx_grep[i][:1],
                                       t_max=t_maxes[i]))
        else:
            mixed.append(ChooseRequest("grep", ctx_grep[i],
                                       t_max=t_maxes[i]))

    async def drive_mixed():
        async with AsyncHubGateway(gw_mixed, max_batch=128) as agw:
            out = await asyncio.gather(*[agw.handle_async(q) for q in mixed])
            assert all(r.ok for r in out)
            return dict(agw.lane_stats)

    asyncio.run(drive_mixed())                                     # warm-up
    t0 = time.time()
    lanes = asyncio.run(drive_mixed())
    mixed_s = time.time() - t0
    per_lane = " ".join(f"{j}:batch={s.mean_batch:.1f}"
                        for j, s in sorted(lanes.items()))
    _row("gateway.multi_job", mixed_s / n_req * 1e6,
         f"requests/s={n_req / mixed_s:.0f} jobs={len(jobs)} "
         f"ops=choose+predict+search+contribute {per_lane}")


def bench_edge(args):
    """Socket-level serving edge vs the in-process gateway.

    One seeded read-only request stream (predict/choose/search over two
    jobs) is played twice against the SAME warm ``HubGateway``:

    ``edge.socket``  closed loop over a real localhost socket — 64
                     keep-alive HTTP/1.1 connections through
                     ``EdgeServer`` + ``HubEdgeApp`` (requests/s, client
                     p50/p95/p99, realized predict-lane mean batch)
    ``edge.inproc``  the same stream through ``AsyncHubGateway``
                     in-process at the same concurrency (the socket
                     path's overhead budget)
    ``edge.parity``  byte-for-byte comparison of every HTTP response
                     body against the codec-encoded in-process envelope

    Hard SystemExit gates (CI smoke): socket requests/s >= 0.5x
    in-process, predict-lane mean batch > 1 under 64 connections, and
    zero parity mismatches.  The full report also lands as JSON in
    ``experiments/edge_bench.json``.
    """
    import asyncio

    from repro.api import AsyncHubGateway, decode, encode
    from repro.serve.edge import _demo_gateway, serve_edge
    from repro.serve.loadgen import _request, build_workload, run_loadgen

    n_req, n_conn, tick_s = 1024, 64, 0.004
    gw = _demo_gateway(("grep", "sort"))
    workload = build_workload(n_req, jobs=("grep", "sort"), seed=0)

    async def capture(host, port, connections=8):
        """Replay the workload collecting each response body by index."""
        out = [b""] * len(workload)

        async def worker(c):
            reader, writer = await asyncio.open_connection(host, port)
            try:
                for k in range(c, len(workload), connections):
                    path, body = workload[k]
                    _, out[k] = await _request(reader, writer, "POST",
                                               path, body)
            finally:
                writer.close()
                try:
                    await writer.wait_closed()
                except (ConnectionResetError, BrokenPipeError):
                    pass

        await asyncio.gather(*(worker(c) for c in range(connections)))
        return out

    reqs = [decode(body.decode("utf-8")) for _, body in workload]

    async def inproc_pass():
        """The same stream through the in-process gateway at the same
        closed-loop concurrency (a semaphore plays the connections)."""
        sem = asyncio.Semaphore(n_conn)

        async def one(agw, q):
            async with sem:
                return await agw.handle_async(q)

        async with AsyncHubGateway(gw, max_batch=256,
                                   tick_s=tick_s) as agw:
            t0 = time.monotonic()
            out = await asyncio.gather(*[one(agw, q) for q in reqs])
            return out, time.monotonic() - t0

    async def socket_pass():
        """The stream over a real localhost socket through a fresh
        edge (clean stats) on the same warm gateway."""
        app, server = await serve_edge(gw, tick_s=tick_s)
        try:
            return await run_loadgen(server.host, server.port,
                                     connections=n_conn,
                                     workload=workload)
        finally:
            await server.stop()

    async def run():
        # warm-up: one full-size pass per path, so every (job, machine)
        # predictor is fit and every realized batch shape is compiled —
        # otherwise whichever path runs later wins on cache warmth
        await inproc_pass()
        await socket_pass()

        # interleaved rep pairs: drift (CI neighbours, GC pauses) hits
        # both paths of a pair alike, so gate on the best PER-PAIR
        # ratio — best-socket-vs-best-inproc across different reps
        # would let uncorrelated noise fail a healthy edge
        report, inproc_out, inproc_s = None, None, math.inf
        best_ratio = -math.inf
        for _ in range(3):
            rep = await socket_pass()
            out, dt = await inproc_pass()
            pair_ratio = rep.rps * dt / n_req
            if pair_ratio > best_ratio:
                best_ratio = pair_ratio
                report, inproc_out, inproc_s = rep, out, dt

        # parity capture: every HTTP response body by workload index
        app, server = await serve_edge(gw, tick_s=tick_s)
        try:
            http_bytes = await capture(server.host, server.port)
        finally:
            await server.stop()
        return report, http_bytes, inproc_out, inproc_s

    report, http_bytes, inproc_out, inproc_s = asyncio.run(run())
    if report.errors:
        raise SystemExit(
            f"edge.socket: {report.errors}/{report.requests} requests "
            "answered error envelopes on a fully-valid workload")
    mean_batch = report.predict_mean_batch()
    _row("edge.socket", report.wall_s / report.requests * 1e6,
         f"requests/s={report.rps:.0f} connections={report.connections} "
         f"p50_ms={report.p50_ms:.1f} p95_ms={report.p95_ms:.1f} "
         f"p99_ms={report.p99_ms:.1f} predict_mean_batch={mean_batch:.2f}")

    inproc_rps = n_req / inproc_s
    ratio = report.rps / inproc_rps
    _row("edge.inproc", inproc_s / n_req * 1e6,
         f"requests/s={inproc_rps:.0f} "
         f"socket_vs_inproc={ratio:.2f}x (target >=0.5x)")

    expected = [encode(r).encode("ascii") for r in inproc_out]
    mismatch = sum(1 for a, b in zip(http_bytes, expected) if a != b)
    _row("edge.parity", 0.0,
         f"identical={n_req - mismatch}/{n_req} "
         "(HTTP body vs in-process envelope, byte-for-byte)")

    os.makedirs("experiments", exist_ok=True)
    with open("experiments/edge_bench.json", "w") as f:
        json.dump({"socket": report.to_json(),
                   "inproc_rps": inproc_rps,
                   "socket_vs_inproc": ratio,
                   "parity_mismatches": mismatch}, f, indent=2,
                  sort_keys=True)

    if mismatch:
        raise SystemExit(
            f"edge.parity: {mismatch}/{n_req} HTTP responses differ from "
            "the in-process gateway on the same seeded stream")
    if mean_batch <= 1.0:
        raise SystemExit(
            f"edge.socket: predict-lane mean batch {mean_batch:.2f} under "
            f"{n_conn} connections — the lanes are not coalescing")
    if ratio < 0.5:
        raise SystemExit(
            f"edge.socket: {report.rps:.0f} req/s is {ratio:.2f}x the "
            f"in-process gateway ({inproc_rps:.0f} req/s); the socket "
            "path must hold >= 0.5x")


def bench_ingest(args):
    """Contribution-ingestion throughput on a 10k-row collaborative store.

    ``ingest.contribute_cold``  first contribution (compiles executables)
    ``ingest.contribute_warm``  steady-state contributions/s and rows/s
    ``ingest.legacy_path``      pre-refactor emulation: O(N) TSV re-encode +
                                re-hash per contribution, fresh CV predictor
                                per machine group, full-copy concat — the
                                headline reports the warm speedup over it
                                (acceptance target >= 10x).
    """
    import hashlib

    from repro.core.datastore import RuntimeDataStore
    from repro.core.features import RuntimeData
    from repro.core.predictor import C3OPredictor
    from repro.workloads import spark_emul as W

    base = W.generate_job_data("grep")
    rng = np.random.default_rng(0)
    n_store, n_delta = 10_000, 20
    idx = np.tile(np.arange(len(base)), -(-n_store // len(base)))[:n_store]
    data = RuntimeData.from_columns(
        base.schema, base.machines, base.codes[idx], base.scale_out[idx],
        base.context[idx],
        base.runtime[idx] * rng.lognormal(0.0, 0.01, n_store))

    def delta():
        j = rng.integers(0, len(base), n_delta)
        return RuntimeData.from_columns(
            base.schema, base.machines, base.codes[j], base.scale_out[j],
            base.context[j],
            base.runtime[j] * rng.lognormal(0.0, 0.01, n_delta))

    store = RuntimeDataStore(data, seed=0)
    t0 = time.time()
    assert store.contribute(delta()).accepted
    cold = time.time() - t0
    _row("ingest.contribute_cold", cold * 1e6,
         f"first contribution at {n_store} stored rows (compiles)")

    reps = 10
    t0 = time.time()
    accepted = sum(store.contribute(delta()).accepted for _ in range(reps))
    warm = (time.time() - t0) / reps
    _row("ingest.contribute_warm", warm * 1e6,
         f"contributions/s={1 / warm:.1f} rows/s={n_delta / warm:.0f} "
         f"accepted={accepted}/{reps} store_rows={len(store)}")

    # --- pre-refactor path: full re-encode/re-hash + fresh CV predictors --
    def legacy_contribute(st, contribution):
        hashlib.sha256(st.data.to_tsv().encode()).hexdigest()  # O(N) rehash
        vrng = np.random.default_rng(st.seed)
        n = len(st.data)
        pidx = vrng.permutation(n)
        test = st.data.subset(pidx[: max(2, n // 5)])
        train = st.data.subset(pidx[max(2, n // 5):][:1024])
        cand = train.concat(contribution)
        for m in dict.fromkeys(contribution.machine_type):
            for dset in (train, cand):
                tr = dset.filter_machine(m)
                te = test.filter_machine(m)
                pred = C3OPredictor(max_cv_folds=15, seed=st.seed) \
                    .fit(tr.X, tr.y)
                p = np.nan_to_num(pred.predict(te.X), nan=1e12, posinf=1e12)
                np.mean(np.abs(p - te.y) / np.maximum(te.y, 1e-9))
        st.data = st.data.concat(contribution)

    store_l = RuntimeDataStore(data, seed=0)
    legacy_contribute(store_l, delta())                        # warm-up
    reps_l = 3
    t0 = time.time()
    for _ in range(reps_l):
        legacy_contribute(store_l, delta())
    legacy = (time.time() - t0) / reps_l
    _row("ingest.legacy_path", legacy * 1e6,
         f"contributions/s={1 / legacy:.1f} "
         f"speedup_warm_vs_legacy={legacy / max(warm, 1e-9):.1f}x "
         "(target >=10x)")


def bench_compact(args):
    """Store lifecycle: coverage-aware compaction of a 10k-row store.

    ``compact.reduce``    one ``compact()`` epoch transition at the default
                          knobs: wall time + rows retained (acceptance
                          gate: >= 4x row reduction)
    ``compact.refit``     warm full-machine refit wall time on the store
                          data before vs after the epoch transition
                          (acceptance gate: >= 2x faster after)
    ``compact.accuracy``  held-out MAPE of predictors fit on the full vs
                          the compacted store — the grid is re-measured
                          under an independent noise draw (acceptance
                          gate: degradation <= 1pp MAPE)

    The gates raise ``SystemExit`` (escaping the harness's per-bench
    except clause) so CI fails loudly when the reduction policy regresses.
    """
    from repro.core.datastore import RuntimeDataStore
    from repro.core.features import RuntimeData
    from repro.core.predictor import C3OPredictor
    from repro.workloads import spark_emul as W

    base = W.generate_job_data("grep")
    rng = np.random.default_rng(0)
    n_store = 10_000
    idx = np.tile(np.arange(len(base)), -(-n_store // len(base)))[:n_store]
    data = RuntimeData.from_columns(
        base.schema, base.machines, base.codes[idx], base.scale_out[idx],
        base.context[idx],
        base.runtime[idx] * rng.lognormal(0.0, 0.01, n_store))
    # held-out truth: the same measurement grid under an independent
    # noise draw — what a NEW reader of the store would need predicted
    test = RuntimeData.from_columns(
        base.schema, base.machines, base.codes, base.scale_out,
        base.context, base.runtime * rng.lognormal(0.0, 0.01, len(base)))
    machines = sorted(dict.fromkeys(data.machine_type))

    def fit_all(d):
        return {m: C3OPredictor(max_cv_folds=10, seed=0)
                .fit(d.machine_view(m).X, d.machine_view(m).y)
                for m in machines}

    def refit_time(d):
        best = math.inf
        for _ in range(2):
            t0 = time.time()
            fit_all(d)
            best = min(best, time.time() - t0)
        return best

    def held_out_mape(preds):
        errs = []
        for m in machines:
            te = test.machine_view(m)
            p = np.nan_to_num(preds[m].predict(te.X), nan=1e12, posinf=1e12,
                              neginf=-1e12)
            errs.append(float(np.mean(
                np.abs(p - te.y) / np.maximum(np.abs(te.y), 1e-9))))
        return float(np.mean(errs))

    store = RuntimeDataStore(data, seed=0)
    mape_full = held_out_mape(fit_all(data))      # also warms executables
    refit_full = refit_time(data)

    t0 = time.time()
    report = store.compact(seed=0)
    compact_s = time.time() - t0
    if not report.accepted:
        raise SystemExit(
            f"compact.reduce: default-knob compaction of the {n_store}-row "
            f"corpus must be accepted, got: {report.reason}")
    reduction = report.rows_before / max(report.rows_after, 1)
    _row("compact.reduce", compact_s * 1e6,
         f"rows={report.rows_before}->{report.rows_after} "
         f"reduction={reduction:.1f}x cells={report.cells} "
         f"epoch={store.epoch} (target >=4x)")
    if reduction < 4.0:
        raise SystemExit(
            f"compact.reduce: {reduction:.1f}x row reduction is below the "
            "4x acceptance floor")

    refit_small = refit_time(store.data)
    speedup = refit_full / max(refit_small, 1e-9)
    _row("compact.refit", refit_small * 1e6,
         f"full_us={refit_full * 1e6:.0f} "
         f"speedup={speedup:.1f}x (target >=2x)")
    if speedup < 2.0:
        raise SystemExit(
            f"compact.refit: warm refit sped up only {speedup:.1f}x; "
            "the epoch transition must buy >= 2x")

    mape_small = held_out_mape(fit_all(store.data))
    delta_pp = (mape_small - mape_full) * 100
    _row("compact.accuracy", compact_s * 1e6,
         f"mape_full={mape_full:.4f} mape_compacted={mape_small:.4f} "
         f"delta={delta_pp:+.2f}pp (target <=+1pp)")
    if delta_pp > 1.0:
        raise SystemExit(
            f"compact.accuracy: compaction degraded held-out MAPE by "
            f"{delta_pp:+.2f}pp (> +1pp budget)")


def bench_eval(args):
    """Collaborative replay plane: wall-clock and accuracy summary.

    A small leave-one-user-out replay (4 users, grep + sort) — enough
    contributions for real trajectories while staying CI-smoke sized.
    Reports per-checkpoint cost, each job's C3O final MAPE vs the
    optimistic/linear baselines, and quartile-median monotonicity; the
    full-scale run is ``python -m repro.eval.replay --users 8``.
    """
    from repro.eval.replay import ReplayConfig, run_replay

    cfg = ReplayConfig(jobs=("grep", "sort"), n_users=4, seed=0,
                       chunks_per_user=2)
    res = run_replay(cfg)
    checkpoints = len({(r["job"], r["held_out"], r["step"])
                       for r in res.records})
    _row("eval.replay", res.wall_s / max(checkpoints, 1) * 1e6,
         f"users={cfg.n_users} jobs={len(cfg.jobs)} "
         f"checkpoints={checkpoints} rows={len(res.records)} "
         f"accepted={res.accepted}/{res.contributions} "
         f"fingerprint={res.fingerprint[:12]} wall_s={res.wall_s:.1f}")
    for job, s in res.summary.items():
        best_base = min(s["baselines"].values())
        _row(f"eval.{job}", res.wall_s * 1e6 / max(checkpoints, 1),
             f"c3o_final={s['c3o_final']:.4f} "
             f"best_baseline={best_base:.4f} monotone={s['monotone']} "
             f"quartiles={'>'.join(f'{q:.3f}' for q in s['quartile_medians'])}")


def bench_trust(args):
    """Trust plane: adversarial-replay value + gateway auth overhead.

    ``trust.adversarial``  small twin-arm poisoned replay (one job, 25%
                           poisoners): final C3O MAPE with reputation
                           weighting off vs on — the improvement IS the
                           trust plane's measured value (the full 5-job
                           acceptance run is ``python -m
                           repro.eval.adversarial``).
    ``trust.auth_overhead``  hot-path cost of token admission: authed vs
                           plain predict requests through the gateway
                           (target <= 5% overhead).
    """
    from repro.api import (AuthedRequest, HubGateway, PredictRequest,
                           TrustAuthority)
    from repro.core.datastore import RuntimeDataStore
    from repro.core.hub import Hub, JobRepo
    from repro.eval.adversarial import AdversarialConfig, run_adversarial
    from repro.workloads import spark_emul as W

    # pagerank at the acceptance run's user mix: single-job smoke with a
    # visible off-vs-on gap (a scale + a noise poisoner slip data past
    # plain validation that reputation weighting then defuses)
    cfg = AdversarialConfig(jobs=("pagerank",), n_users=8,
                            poison_fraction=0.25, seed=0, chunks_per_user=2)
    res = run_adversarial(cfg)
    s = res.summary["pagerank"]
    _row("trust.adversarial", res.wall_s * 1e6 / max(res.contributions, 1),
         f"users={cfg.n_users} poisoners={len(cfg.poisoners())} "
         f"off_final={s['off_final']:.4f} on_final={s['on_final']:.4f} "
         f"improvement={s['improvement']:.4f} ok={s['ok']} "
         f"accepted={res.accepted}/{res.contributions} "
         f"fingerprint={res.fingerprint[:12]} wall_s={res.wall_s:.1f}")
    if not s["ok"]:
        # a hard acceptance gate, not a reported target: SystemExit
        # escapes the harness's per-bench except clause and fails CI
        raise SystemExit(
            "trust.adversarial: reputation weighting must strictly beat "
            f"weighting-off (off={s['off_final']:.4f} on={s['on_final']:.4f})")

    # --- auth admission overhead on the serving hot path ------------------
    prices = {m.name: m.price for m in W.MACHINES.values()}
    d = W.generate_job_data("grep")
    hub = Hub()
    hub.publish(JobRepo("grep", "grep", d.schema, RuntimeDataStore(d)))
    auth = TrustAuthority(rate=1e9, burst=1e9)     # meter, never refuse
    gw_plain = HubGateway(hub, prices, [2, 4, 8])
    gw_auth = HubGateway(hub, prices, [2, 4, 8], auth=auth)
    token = gw_auth.issue_token("bench")
    req = PredictRequest("grep", "m5.xlarge", ((4.0, 15.0, 0.02),))
    wrapped = AuthedRequest(token, req)
    gw_plain.predict(req)                          # warm the predictor
    gw_auth.predict(wrapped)
    n = 2000
    plain_s = authed_s = math.inf
    for _ in range(3):                             # interleaved best-of-reps
        t0 = time.time()
        for _ in range(n):
            gw_plain.predict(req)
        plain_s = min(plain_s, time.time() - t0)
        t0 = time.time()
        for _ in range(n):
            gw_auth.predict(wrapped)
        authed_s = min(authed_s, time.time() - t0)
    _row("trust.auth_overhead", authed_s / n * 1e6,
         f"plain_us={plain_s / n * 1e6:.1f} "
         f"overhead={(authed_s / plain_s - 1) * 100:+.1f}% (target <=5%)")


def bench_transfer(args):
    """Cold-start cross-job transfer: borrowed accuracy + lookup cost.

    ``transfer.lookup``    nearest-donor lookup on the hub's transfer
                           index: cold (sketches every store) vs warm
                           (unchanged store versions — pure cache hits);
                           flat signature-build/pair-eval counters across
                           the warm reps are a hard SystemExit gate
    ``transfer.borrowed``  MAPE of the gateway's borrowed predictions on
                           a zero-history twin job's full ground truth
                           vs the global-mean no-history baseline (what a
                           hub without transfer could answer) — borrowing
                           must beat it (hard SystemExit gate)
    """
    from repro.api import HubGateway, PredictRequest
    from repro.core.datastore import RuntimeDataStore
    from repro.core.hub import Hub, JobRepo
    from repro.core.transfer import TransferPolicy
    from repro.workloads import spark_emul as W

    prices = {m.name: m.price for m in W.MACHINES.values()}
    donors = ("sgd", "kmeans", "pagerank")   # schema-compatible donor pool
    hub = Hub()
    stores = {}
    for job in donors:
        d = W.generate_job_data(job, seed=0)
        stores[job] = RuntimeDataStore(d, seed=0)
        hub.publish(JobRepo(job, job, d.schema, stores[job],
                            predictor_kw={"max_cv_folds": 15}))
    cold = W.cold_job_name("sgd")
    hub.publish(JobRepo(cold, "sgd (cold twin)", W.cold_schema("sgd"),
                        RuntimeDataStore(W.cold_probe("sgd", 0), seed=0)))
    pol = TransferPolicy()
    gw = HubGateway(hub, prices, (2, 3, 4, 6, 8, 12), transfer=pol)

    # --- lookup cost: cold sketch vs version-keyed cache hits -------------
    index = hub.transfer_index(pol)
    t0 = time.time()
    match = index.nearest(cold)
    cold_us = (time.time() - t0) * 1e6
    builds = index.stats["signature_builds"]
    pairs = index.stats["pair_evals"]
    reps = 200
    t0 = time.time()
    for _ in range(reps):
        index.nearest(cold)
    warm_us = (time.time() - t0) / reps * 1e6
    _row("transfer.lookup", warm_us,
         f"cold_us={cold_us:.0f} warm_us={warm_us:.1f} "
         f"amortized={cold_us / max(warm_us, 1e-9):.0f}x "
         f"source={match.source} sim={match.similarity:.3f}")
    if index.stats["signature_builds"] != builds \
            or index.stats["pair_evals"] != pairs:
        raise SystemExit(
            "transfer.lookup: repeated lookups against unchanged store "
            "versions re-sketched "
            f"({index.stats['signature_builds'] - builds} builds, "
            f"{index.stats['pair_evals'] - pairs} pair evals) — the "
            "version-keyed caches are not amortizing")

    # --- borrowed accuracy vs the no-history global-mean baseline ---------
    test = W.generate_cold_job_data("sgd", seed=0)
    gmean = float(np.concatenate(
        [s.data.runtime for s in stores.values()]).mean())
    errs_b, errs_m = [], []
    n_rows, confidence = 0, 0.0
    t0 = time.time()
    for machine in sorted(test.present_machines()):
        te = test.machine_view(machine)
        y = np.asarray(te.y, np.float64)
        resp = gw.predict(PredictRequest(
            cold, machine, tuple(tuple(r) for r in te.X.tolist()), seed=0))
        if not resp.ok:
            raise SystemExit(
                f"transfer.borrowed: predict for {cold!r} on {machine!r} "
                f"failed: {resp.error_code}: {resp.detail}")
        p = np.asarray(resp.result.runtimes_s, np.float64)
        errs_b.append(float(np.mean(np.abs(p - y) / y)))
        errs_m.append(float(np.mean(np.abs(gmean - y) / y)))
        n_rows += len(y)
        confidence = resp.result.transfer_confidence
    dt = time.time() - t0
    mape_b, mape_m = float(np.mean(errs_b)), float(np.mean(errs_m))
    _row("transfer.borrowed", dt / max(n_rows, 1) * 1e6,
         f"source={match.source} confidence={confidence:.3f} "
         f"borrowed_mape={mape_b:.4f} mean_mape={mape_m:.4f} "
         f"rows={n_rows} (target: borrowed < mean)")
    if mape_b >= mape_m:
        raise SystemExit(
            f"transfer.borrowed: borrowed MAPE {mape_b:.4f} does not beat "
            f"the global-mean no-history baseline {mape_m:.4f}")


def bench_market(args):
    """Cloud market plane: interruption-adjusted placement selection.

    ``market.replay``     seeded spot-market replay (5 job families):
                          interruption-adjusted choice vs the naive
                          cheapest-listed-price baseline on REALIZED
                          completion cost — adjusted must win on every
                          family (hard SystemExit gate)
    ``market.grid_axis``  warm ``choose_cluster_batch`` wall-clock with
                          the full Z-zone placement axis (3 zones x 2
                          purchase options) vs a flat single-placement
                          book — the axis is vectorized broadcasting on
                          the same fused dispatch, so it must stay
                          within 2x (hard SystemExit gate)
    """
    from repro.core.datastore import RuntimeDataStore
    from repro.core.hub import JobRepo
    from repro.core.market import PriceBook
    from repro.core.service import ConfigurationService
    from repro.eval.replay import SpotMarketConfig, run_spot_market
    from repro.workloads import spark_emul as W

    # --- realized-cost win over the naive cheapest-price baseline ---------
    cfg = SpotMarketConfig(n_queries=10)     # CI-smoke sized
    res = run_spot_market(cfg)
    n_choices = 2 * cfg.n_queries * len(cfg.jobs)
    worst = min(res.summary.values(), key=lambda s: s["savings"])
    _row("market.replay", res.wall_s / n_choices * 1e6,
         f"families={len(res.summary)} "
         f"savings_worst={worst['savings']:.2f}x "
         f"diverged={sum(s['diverged'] for s in res.summary.values())}"
         f"/{sum(s['queries'] for s in res.summary.values())} "
         f"fingerprint={res.fingerprint[:12]} (target: adjusted < naive "
         "realized cost on every family)")
    for job, s in sorted(res.summary.items()):
        _row(f"market.{job}", 0.0,
             f"adjusted=${s['adjusted_cost']:.4f} "
             f"naive=${s['naive_cost']:.4f} savings={s['savings']:.2f}x "
             f"diverged={s['diverged']}/{s['queries']}")
    if not res.ok:
        losers = [j for j, s in res.summary.items() if not s["ok"]]
        raise SystemExit(
            "market.replay: interruption-adjusted selection does not "
            "beat the naive cheapest-listed-price baseline on realized "
            f"cost for: {', '.join(losers)}")

    # --- placement axis is broadcasting, not a loop -----------------------
    data = W.generate_job_data("grep", seed=0)
    repo = JobRepo("grep", "grep", data.schema,
                   RuntimeDataStore(data, seed=0),
                   predictor_kw={"max_cv_folds": 15})
    preds = {m: repo.predictor_for(m) for m in sorted(W.MACHINES)}
    prices = {m.name: m.price for m in W.MACHINES.values()}
    scaleouts = (2, 3, 4, 6, 8, 12)
    flat_svc = ConfigurationService(preds, {}, scaleouts,
                                    market=PriceBook.flat(prices))
    full_svc = ConfigurationService(preds, {}, scaleouts,
                                    market=W.generate_price_book(0))
    ctx = np.stack([np.array([15.0 * (1 + 0.05 * i), 0.02])
                    for i in range(64)])

    def best_of(svc, reps=5):
        svc.choose_cluster_batch(ctx)                      # warm-up
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            svc.choose_cluster_batch(ctx)
            best = min(best, time.time() - t0)
        return best

    flat_s, full_s = best_of(flat_svc), best_of(full_svc)
    z = len(full_svc.market.placements)
    ratio = full_s / max(flat_s, 1e-12)
    _row("market.grid_axis", full_s / len(ctx) * 1e6,
         f"placements={z} flat_us={flat_s * 1e6:.0f} "
         f"full_us={full_s * 1e6:.0f} ratio={ratio:.2f}x "
         "(target: <= 2x — a vectorized axis, not a loop)")
    if ratio > 2.0:
        raise SystemExit(
            f"market.grid_axis: scoring {z} placements costs "
            f"{ratio:.2f}x the single-placement grid (> 2x): the "
            "placement axis is not amortizing like a vectorized axis")


def bench_table1(args):
    from repro.workloads import spark_emul as W
    t0 = time.time()
    data = W.generate_all()
    total = sum(len(d) for d in data.values())
    per = ";".join(f"{j}:{len(d)}" for j, d in data.items())
    _row("table1.dataset", (time.time() - t0) * 1e6 / max(total, 1),
         f"total={total} (paper:930) {per}")


def bench_table2(args):
    from benchmarks.common import JOBS, PAPER_TABLE2, run_scenario
    for job in JOBS:
        for scenario in (("local", "global") if job != "sort"
                         else ("global",)):
            t0 = time.time()
            r = run_scenario(job, scenario, n_splits=args.splits)
            dt = (time.time() - t0) * 1e6 / args.splits
            for model in ("ernest", "gbm", "bom", "ogb", "c3o"):
                paper = PAPER_TABLE2[job][model][scenario != "local"]
                _row(f"table2.{job}.{scenario}.{model}", dt,
                     f"mape={r[model]:.4f} paper={paper:.4f}")


def bench_fig5(args):
    from benchmarks.common import MODELS, TARGET_MACHINE
    from repro.core.predictor import evaluate_split
    from repro.workloads import spark_emul as W
    sizes = [3, 6, 9, 12, 15, 18, 21, 24, 27, 30]
    n_splits = max(args.splits // 4, 10)
    for job in ("grep", "kmeans"):          # representative pair of panels
        data = W.generate_job_data(job).filter_machine(TARGET_MACHINE)
        rng = np.random.default_rng(1)
        for n in sizes:
            t0 = time.time()
            errs = {}
            for i in range(n_splits):
                idx = rng.permutation(len(data))
                tr, te = idx[:n], idx[n:]
                r = evaluate_split(MODELS, data.X[tr], data.y[tr],
                                   data.X[te], data.y[te],
                                   max_cv_folds=min(n, 10), seed=i)
                for k, v in r.items():
                    if k != "c3o_selected":
                        errs.setdefault(k, []).append(v)
            dt = (time.time() - t0) * 1e6 / n_splits
            summary = " ".join(
                f"{m}={np.mean(np.minimum(errs[m], 10.0)):.3f}"
                for m in ("ernest", "gbm", "bom", "ogb", "c3o"))
            _row(f"fig5.{job}.n{n}", dt, summary)


def bench_configurator(args):
    from repro.core.configurator import Configurator
    from repro.core.predictor import C3OPredictor
    from repro.workloads import spark_emul as W
    prices = {m.name: m.price for m in W.MACHINES.values()}
    scaleouts = [2, 3, 4, 6, 8, 12, 16]
    rng = np.random.default_rng(0)
    for job, ctx_fn in (("grep", lambda: (rng.uniform(10, 20),
                                          rng.choice([.002, .02, .08]))),
                        ("sgd", lambda: (rng.uniform(10, 30),
                                         rng.choice([5, 20, 40, 70, 100]),
                                         rng.choice([50, 100])))):
        d = W.generate_job_data(job).filter_machine("m5.xlarge")
        pred = C3OPredictor(max_cv_folds=25).fit(d.X, d.y)
        conf = Configurator(pred, "m5.xlarge", prices, scaleouts,
                            confidence=0.95)
        hits = total = 0
        cost_c3o = cost_max = 0.0
        t0 = time.time()
        for _ in range(60):
            ctx = np.asarray(ctx_fn(), dtype=float)
            feasible_t = [W.true_runtime(job, "m5.xlarge", s, tuple(ctx))
                          for s in scaleouts]
            t_max = float(rng.uniform(1.15, 2.0) * min(feasible_t))
            ch = conf.choose_scaleout(ctx, t_max=t_max)
            truth = feasible_t[scaleouts.index(ch.scale_out)]
            total += 1
            hits += truth <= t_max
            cost_c3o += prices["m5.xlarge"] * truth / 3600 * ch.scale_out
            cost_max += prices["m5.xlarge"] * feasible_t[-1] / 3600 \
                * scaleouts[-1]
        dt = (time.time() - t0) * 1e6 / total
        _row(f"configurator.{job}", dt,
             f"deadline_hit={hits/total:.3f} (target>=0.95) "
             f"cost_vs_overprovision={cost_c3o/cost_max:.3f}")


def bench_autoconfig(args):
    from repro.configs import SHAPES, get_config
    from repro.launch.autoconfig import (SLICES, autoconfigure,
                                         predicted_step_time)
    for arch, shape in (("gemma3-1b", "train_4k"),
                        ("deepseek-7b", "train_4k"),
                        ("kimi-k2-1t-a32b", "train_4k")):
        t0 = time.time()
        choice, pred = autoconfigure(arch, shape,
                                     chip_counts=(64, 128, 256, 512))
        dt = (time.time() - t0) * 1e6
        cfg = get_config(arch)
        true_t = predicted_step_time(cfg, SHAPES[shape], SLICES["v5e"],
                                     choice.scale_out)
        err = abs(choice.predicted_runtime_s - true_t) / true_t
        _row(f"autoconfig.{arch}", dt,
             f"chips={choice.scale_out} model={pred.selected} "
             f"step_pred={choice.predicted_runtime_s*1e3:.0f}ms "
             f"pred_err={err:.3f}")


def bench_kernels(args):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref as R
    from repro.kernels.flash_attention import flash_attention
    key = jax.random.PRNGKey(0)
    B, S, H, KV, hd = 1, 512, 4, 2, 64

    def timed(fn, *a, n=3, **kw):
        fn(*a, **kw)           # compile/warm
        t0 = time.time()
        for _ in range(n):
            jax.block_until_ready(fn(*a, **kw))
        return (time.time() - t0) / n * 1e6

    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    ref_t = timed(jax.jit(lambda q, k, v: R.attention_ref(q, k, v)), q, k, v)
    _row("kernels.attention_ref_jit", ref_t, "oracle (XLA:CPU)")
    pal_t = timed(lambda q, k, v: flash_attention(
        q, k, v, q_block=128, kv_block=128, interpret=True), q, k, v, n=1)
    _row("kernels.flash_attention_interpret", pal_t,
         "correctness path (TPU kernel interpreted on CPU)")

    r_ = jax.random.normal(ks[0], (B, 256, H, 32)) * 0.5
    k_ = jax.random.normal(ks[1], (B, 256, H, 32)) * 0.5
    v_ = jax.random.normal(ks[2], (B, 256, H, 32)) * 0.5
    w_ = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, 256, H, 32)) * 0.5))
    u_ = jnp.zeros((H, 32))
    seq_t = timed(jax.jit(lambda *a: R.wkv6_ref(*a)[0]), r_, k_, v_, w_, u_)
    chk_t = timed(jax.jit(lambda *a: R.wkv6_chunked_ref(*a)[0]),
                  r_, k_, v_, w_, u_)
    _row("kernels.wkv6_sequential_ref", seq_t, "token-recurrent oracle")
    _row("kernels.wkv6_chunked_jnp", chk_t,
         f"chunked form, speedup={seq_t/max(chk_t,1e-9):.1f}x over sequential")


def bench_roofline(args):
    recs = []
    for p in sorted(glob.glob("experiments/dryrun_*.json")):
        with open(p) as f:
            recs.extend(json.load(f))
    ok = [r for r in recs if r.get("status") == "ok"
          and r["mesh"] == "16x16"]
    if not ok:
        _row("roofline", 0.0, "no dryrun records yet (run launch.dryrun)")
        return
    for r in ok:
        rl = r["roofline"]
        _row(f"roofline.{r['arch']}.{r['shape']}", r["compile_s"] * 1e6,
             f"dom={rl['dominant']} bound_ms={rl['bound_s']*1e3:.1f} "
             f"compute_ms={rl['compute_s']*1e3:.1f} "
             f"mem_ms={rl['memory_s']*1e3:.1f} "
             f"coll_ms={rl['collective_s']*1e3:.1f} "
             f"useful={r['useful_flops_ratio']:.2f} "
             f"fits={r['fits_hbm']}")


BENCHES = {
    "engine": bench_engine,
    "serve": bench_serve,
    "gateway": bench_gateway,
    "edge": bench_edge,
    "ingest": bench_ingest,
    "compact": bench_compact,
    "eval": bench_eval,
    "trust": bench_trust,
    "transfer": bench_transfer,
    "market": bench_market,
    "table1": bench_table1,
    "table2": bench_table2,
    "fig5": bench_fig5,
    "configurator": bench_configurator,
    "autoconfig": bench_autoconfig,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--splits", type=int, default=60)
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        try:
            fn(args)
        except Exception as e:       # report, keep the harness going
            _row(f"{name}.ERROR", 0.0, f"{type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
