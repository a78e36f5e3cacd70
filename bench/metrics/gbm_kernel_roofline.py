"""Share of its roofline the GBM inference kernel reaches in the window,
in percent: the least time the chip needs for the kernel's work (the
larger of operations over peak FLOP/s and bytes over HBM bandwidth,
``bench/peaks.json``) over the device time of the kernel's events.

The work is counted from the algorithm, not from the kernel, so a later
kernel is read against the same work: every row scored walks every tree
to its depth (rows x trees x depth compare-and-branch steps), reads its
features and writes one f32.  Rows are those the served answers needed
from GBM-selected predictors: each choose scores the scale-out grid on
every machine whose predictor selected GBM (the hub answers which
through ``/v1/predict``), each predict one row.
"""
from bench.peaks import peaks

#: the kernel's Pallas call in the trace: a ``tpu_custom_call`` named
#: after ``engine._gbm_kernel_executable``'s jitted ``run``
NAMES = ("run.",)


def work(rows: int, n_trees: int, depth: int, n_features: int) -> tuple:
    """(operations, bytes) of scoring ``rows`` rows."""
    return rows * n_trees * depth, rows * (n_features + 1) * 4


def read(ctx):
    tr = ctx.trace
    if not tr or not ctx.selected:
        return None
    t = sum(v for k, v in tr["kernel_s"].items()
            if any(k.startswith(n) for n in NAMES))
    if t <= 0:
        return None
    gbm = ctx.cfg["gbm"]
    S = len(ctx.cfg["scaleouts"])
    ops = byts = 0
    for r in ctx.traffic.all():
        if not r.ok:
            continue
        if r.op == "predict":
            rows = 1 if r.result.selected_model == "gbm" else 0
        else:
            rows = S * sum(1 for (j, _), sel in ctx.selected.items()
                           if j == r.job and sel == "gbm")
        o, b = work(rows, gbm["n_trees"], gbm["depth"], len(r.context) + 1
                    if r.op != "predict" else len(r.context))
        ops, byts = ops + o, byts + b
    if not ops:
        return None
    p = peaks(ctx.ready["device"]["kind"])
    return 100.0 * max(ops / p["flops_per_s"],
                       byts / p["hbm_bytes_per_s"]) / t
