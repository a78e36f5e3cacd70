"""Batched serving example: prefill + autoregressive decode with KV caches
(ring buffers on sliding-window layers, int8 quantization optional).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import enable_compile_cache
from repro.configs import smoke_config
from repro.modeling import model as M
from repro.serve.serve_step import greedy_generate


def main():
    enable_compile_cache()
    for kv_dtype in ("", "int8"):
        cfg = smoke_config("gemma3-1b", kv_cache_dtype=kv_dtype)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        B, S0 = 4, 16
        prompt = jax.random.randint(jax.random.PRNGKey(1), (B, S0), 0,
                                    cfg.vocab_size)
        toks = greedy_generate(cfg, params, prompt, max_new=24, max_seq=64)
        tag = kv_dtype or "bf16/fp32"
        print(f"kv_cache={tag:9s} generated {toks.shape[1]} tokens/req "
              f"x {B} requests: {toks[0][:10].tolist()}...")


if __name__ == "__main__":
    main()
