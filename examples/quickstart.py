"""Quickstart: the paper's full workflow (Fig. 4) in one script.

1. search the hub for a job  2. download shared runtime data
3-4. provide inputs          5. get a cluster configuration
6. contribute your run's metrics back.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.core import Hub, JobRepo, RuntimeDataStore
from repro.core.features import RuntimeData
from repro.workloads import spark_emul as W


def main():
    enable_compile_cache()
    # --- maintainers publish job repos with shared runtime data ----------
    hub = Hub()
    for job in ("sort", "grep", "kmeans"):
        data = W.generate_job_data(job)
        hub.publish(JobRepo(job, f"apache spark {job}", data.schema,
                            RuntimeDataStore(data)))

    # --- (1) the user searches for an algorithm --------------------------
    repo = hub.search("grep")[0]
    print(f"found job '{repo.job}' with {len(repo.store)} shared runs")

    # --- (2-5) configure a cluster for the user's dataset + deadline -----
    prices = {m.name: m.price for m in W.MACHINES.values()}
    conf = repo.configurator("m5.xlarge", prices,
                             scaleouts=[2, 3, 4, 6, 8, 12])
    ctx = np.asarray([18.0, 0.02])      # 18 GB dataset, 2% keyword hits
    print("\nruntime/cost menu (scale-out, est. seconds, $):")
    for s, t_s, cost in conf.runtime_cost_pairs(ctx):
        print(f"  {s:3d} nodes   {t_s:7.1f}s   ${cost:.4f}")
    choice = conf.choose_scaleout(ctx, t_max=420.0)
    print(f"\ndeadline 420s @95% confidence -> {choice.scale_out} nodes "
          f"(bound {choice.runtime_bound_s:.0f}s, ${choice.cost_usd:.4f})")

    # --- run it (emulated) and (6) contribute the measurement ------------
    measured = W._measure("grep", "m5.xlarge", choice.scale_out,
                          (18.0, 0.02), seed=123)
    print(f"measured runtime: {measured:.1f}s "
          f"({'deadline met' if measured <= 420 else 'MISSED'})")
    new = RuntimeData(repo.schema, np.asarray(["m5.xlarge"]),
                      np.asarray([[choice.scale_out, 18.0, 0.02]]),
                      np.asarray([measured]))
    report = repo.contribute(new, contributor="quickstart-user")
    print(f"contribution validation: accepted={report.accepted} "
          f"({report.reason})")

    # --- the same loop through the API v1 gateway (canonical surface) ----
    from repro.api import (ChooseRequest, ContributeRequest, SearchRequest)
    gw = hub.gateway(prices, scaleouts=(2, 3, 4, 6, 8, 12))
    hit = gw.search(SearchRequest("grep")).result.jobs[0]
    resp = gw.choose(ChooseRequest(hit.job, (18.0, 0.02), t_max=420.0))
    c = resp.result
    print(f"\ngateway: {hit.job} -> {c.machine_type} x{c.scale_out} "
          f"(bound {c.runtime_bound_s:.0f}s, ${c.cost_usd:.4f})")
    measured = W._measure("grep", c.machine_type, c.scale_out,
                          (18.0, 0.02), seed=124)
    out = gw.contribute(ContributeRequest(
        hit.job, (c.machine_type,),
        ((float(c.scale_out), 18.0, 0.02),), (measured,),
        contributor_id="quickstart-user")).result
    print(f"gateway contribution: accepted={out.accepted} "
          f"store_rows={out.store_rows} by {out.contributor_id}")


if __name__ == "__main__":
    main()
