"""C3O-for-TPU: pick a pod slice + chip count for a training workload from
collaboratively shared step-time records (the paper's technique applied to
this framework's own scheduling problem).

Run:  PYTHONPATH=src python examples/autoconfigure_cluster.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.launch.autoconfig import autoconfigure


def main():
    enable_compile_cache()
    for arch, budget in (("gemma3-1b", None),
                         ("deepseek-7b", 0.8),
                         ("kimi-k2-1t-a32b", None)):
        choice, pred = autoconfigure(arch, "train_4k",
                                     step_budget_s=budget,
                                     chip_counts=(64, 128, 256, 512))
        b = f"{budget}s" if budget else "cheapest"
        print(f"{arch:18s} budget={b:9s} -> {choice.scale_out:4d} chips "
              f"(model={pred.selected}, step={choice.predicted_runtime_s*1e3:.0f}ms, "
              f"CV mape={pred.cv_mape[pred.selected]:.3f})")


if __name__ == "__main__":
    main()
