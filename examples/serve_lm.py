"""Serving demo: continuous batching with the paper's adaptive admission.

Submits a burst of requests with mixed prompt lengths and prints per-
request TTFT plus the batcher's admission trajectory — watch k grow
geometrically (c = 1.5) while rounds stay inside [T_min, T_max], the
transplanted Algorithm 1.

    PYTHONPATH=src python examples/serve_lm.py
"""
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.models import get_config, init_params
from repro.serving import AdaptiveRequestBatcher, ServeEngine


def main():
    use_compile_cache()
    cfg = get_config("llcysa-analytics-100m", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batcher = AdaptiveRequestBatcher(k0=1, c=1.5, t_min=0.02, t_max=0.25, max_batch=8)
    eng = ServeEngine(cfg, params, max_batch=8, cache_len=128, batcher=batcher)

    rng = np.random.default_rng(0)
    n_req = 24
    for i in range(n_req):
        eng.submit(
            rng.integers(0, cfg.vocab_size, int(rng.integers(4, 48))),
            max_new_tokens=int(rng.integers(4, 16)),
        )
    done = eng.run()

    print(f"served {len(done)}/{n_req} requests")
    ttfts = sorted(r.ttft for r in done)
    print(f"TTFT p50={1e3*ttfts[len(ttfts)//2]:.1f} ms  p95={1e3*ttfts[int(0.95*len(ttfts))]:.1f} ms")
    print("\nadmission rounds (round_time_s, served):")
    for t, served in batcher.history[:16]:
        print(f"  {t:7.3f}s  batch={served}")
    print(f"final adaptive k = {batcher.k:.1f}")


if __name__ == "__main__":
    main()
