"""End-to-end training driver: events -> store -> tokens -> LM.

Trains the LLCySA analytics LM (next-event prediction) on tokenized web
proxy events drawn from the sharded store, with checkpointing + resume.

    PYTHONPATH=src python examples/train_lm.py                 # CPU-sized
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

The 100m preset is the real ~100M-parameter config (configs/llcysa.py);
the default 'mini' preset shrinks it so the example finishes in minutes on
this container's single CPU core. Both run the identical code path.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.checkpointing import CheckpointManager
from repro.core import EventStore, web_proxy_schema
from repro.models import get_config, init_params
from repro.models.model import forward_train
from repro.pipeline import IngestWorkerPool, SyntheticWebProxySource
from repro.pipeline.tokenizer import EventTokenizer
from repro.training.optimizer import OptConfig, adamw_init, adamw_update


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["mini", "100m"], default="mini")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_config("llcysa-analytics-100m")
    if args.preset == "mini":
        cfg = cfg.replace(n_layers=4, d_model=256, n_heads=8, n_kv_heads=8, d_ff=768)
    print(f"model: {cfg.name} ({cfg.param_count()/1e6:.1f}M params, preset={args.preset})")

    # --- the paper's pipeline feeds training ---
    print("staging + ingesting events ...")
    src = SyntheticWebProxySource(seed=3)
    import tempfile

    files = src.write_files(tempfile.mkdtemp(), 8, 8000, 0, 8 * 3600)
    store = EventStore(web_proxy_schema(), n_shards=4)
    pool = IngestWorkerPool(store, n_workers=2)
    for f in files:
        pool.submit_file(f)
    pool.drain()
    print(f"store: {store.total_rows} events")

    tok = EventTokenizer(store, vocab_size=cfg.vocab_size)
    batches = tok.sequences(0, 8 * 3600, seq_len=args.seq + 1, batch=args.batch)

    opt_cfg = OptConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps)
    params = init_params(jax.random.PRNGKey(0), cfg)
    state = adamw_init(params, opt_cfg)
    mgr = CheckpointManager(args.ckpt_dir, keep=3)
    start_step = 0
    if args.resume and mgr.latest_step() is not None:
        start_step, params = mgr.restore_latest(params)
        print(f"resumed from step {start_step}")

    @jax.jit
    def step(p, s, b):
        (loss, metrics), grads = jax.value_and_grad(
            lambda pp: forward_train(pp, cfg, b, remat=False), has_aux=True
        )(p)
        p, s, om = adamw_update(p, grads, s, opt_cfg)
        return p, s, loss, om["grad_norm"]

    t0 = time.perf_counter()
    tokens_seen = 0
    for i in range(start_step, args.steps):
        raw = next(batches)
        batch = {
            "inputs": jnp.asarray(raw[:, :-1]),
            "targets": jnp.asarray(raw[:, 1:]),
        }
        params, state, loss, gnorm = step(params, state, batch)
        tokens_seen += args.batch * args.seq
        if i % 10 == 0 or i == args.steps - 1:
            dt = time.perf_counter() - t0
            print(
                f"step {i:4d}  loss {float(loss):.4f}  |g| {float(gnorm):.3f}  "
                f"{tokens_seen / max(dt, 1e-9):,.0f} tok/s"
            )
        if (i + 1) % args.ckpt_every == 0:
            mgr.save(i + 1, params)
    mgr.wait()
    print(f"done; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
