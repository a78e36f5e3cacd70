"""End-to-end training driver example: a ~100M-parameter gemma3-family model
for a few hundred steps on CPU/host devices, with checkpointing, crash
recovery and C3O runtime capture.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
(defaults to 60 steps to stay quick; pass --steps 300 for the full curve)
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.train import run as train_run


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--hundred-m", action="store_true",
                    help="full ~100M config (use on real accelerators; the "
                    "CPU default is a ~20M variant of the same family)")
    args = ap.parse_args()

    import dataclasses
    import repro.configs  # noqa: F401
    from repro.configs.base import _REGISTRY
    base = get_config("gemma3-1b")
    if args.hundred_m:   # ~100M params, gemma3 family
        cfg = dataclasses.replace(
            base, n_layers=12, d_model=512, n_heads=4, n_kv_heads=1,
            head_dim=128, d_ff=2048, vocab_size=32768, window_size=256,
            dtype="float32", param_dtype="float32", remat="none",
            grad_accum=1, attention_impl="reference")
        batch, seq = 8, 256
    else:                # ~20M CPU-friendly variant, same code paths
        cfg = dataclasses.replace(
            base, n_layers=6, d_model=256, n_heads=4, n_kv_heads=1,
            head_dim=64, d_ff=1024, vocab_size=8192, window_size=64,
            dtype="float32", param_dtype="float32", remat="none",
            grad_accum=1, attention_impl="reference")
        batch, seq = 4, 128
    _REGISTRY["gemma3-example"] = lambda: cfg
    n = cfg.param_counts()["total"] / 1e6
    print(f"training gemma3-example (~{n:.0f}M params) for {args.steps} steps")

    losses = train_run("gemma3-example", steps=args.steps, batch=batch,
                       seq=seq, ckpt_dir=args.ckpt_dir, smoke=False,
                       ckpt_every=20,
                       runtime_log="/tmp/repro_runtime_log.jsonl")
    k = max(len(losses) // 10, 1)
    for i in range(0, len(losses), k):
        print(f"  step {i:4d}  loss {losses[i]:.4f}")
    print(f"  final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
    assert losses[-1] < losses[0], "loss must decrease"


if __name__ == "__main__":
    main()
