"""Batched serving demo: continuous batching over KV-cache slots.

    PYTHONPATH=src python examples/serve_lm.py
"""
import numpy as np

import jax

from repro.configs import ARCHS, smoke_config
from repro.models.registry import get_model
from repro.serve.engine import Request, ServeEngine

cfg = smoke_config(ARCHS["qwen2-7b"]).scaled(d_model=128, n_layers=4)
api = get_model(cfg)
params = api.init(jax.random.PRNGKey(0))

engine = ServeEngine(api, params, slots=4, max_len=96, temperature=0.0)
rng = np.random.default_rng(0)
for rid in range(10):
    prompt = rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12),
                          dtype=np.int32)
    engine.submit(Request(rid=rid, prompt=prompt, max_new_tokens=8))

results = engine.run_to_completion()
for rid in sorted(results):
    print(f"request {rid}: {results[rid]}")
assert len(results) == 10
print("serve_lm complete ✓ (10 requests, 4 slots, continuous batching)")
