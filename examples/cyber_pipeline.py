"""The full LLCySA-style situational-awareness loop, end to end:

  1. stage raw web-proxy logs on the 'central filesystem'
  2. master queue + parallel ingest workers -> sharded 3-table store
     (with a simulated worker failure: the lease expires and re-queues)
  3. analyst queries via the planner + adaptive batching
  4. events -> tokens -> train the analytics LM a few steps
  5. score a suspicious traffic window by LM perplexity (the 'analytic')

    PYTHONPATH=src python examples/cyber_pipeline.py
"""
import sys
import tempfile
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import And, Eq, EventStore, QueryProcessor, QueryStats, web_proxy_schema
from repro.models import get_config, init_params
from repro.models.model import forward_train
from repro.pipeline import IngestWorkerPool, SyntheticWebProxySource
from repro.pipeline.tokenizer import EventTokenizer
from repro.training.optimizer import OptConfig, adamw_init, adamw_update


def main():
    use_compile_cache()
    print("== 1. stage raw logs ==")
    src = SyntheticWebProxySource(seed=11)
    staged = src.write_files(tempfile.mkdtemp(), n_files=6, lines_per_file=5000, t_start=0, t_stop=4 * 3600)
    print(f"   {len(staged)} files staged")

    print("== 2. parallel ingest (with a mid-run worker failure) ==")
    store = EventStore(web_proxy_schema(), n_shards=4)
    # Lease timeout must comfortably exceed the heartbeat period, or live
    # workers' files re-queue (at-least-once semantics -> duplicates).
    pool = IngestWorkerPool(store, n_workers=3, lease_timeout_s=10.0)
    pool.kill_worker(0)  # node failure: its lease will expire + re-queue
    t0 = time.perf_counter()
    for f in staged:
        pool.submit_file(f)
    reports = pool.drain()
    dt = time.perf_counter() - t0
    print(f"   {store.total_rows} events in {dt:.1f}s despite 1 dead worker "
          f"({sum(r.files for r in reports)} files completed)")
    assert store.total_rows == 30_000

    print("== 3. analyst queries (planner + adaptive batching) ==")
    qp = QueryProcessor(store)
    dom = src.domain_by_popularity(0.02)
    q = And(Eq("domain", dom), Eq("status", "404"))
    stats = QueryStats()
    rows = sum(b.n for b in qp.run_scheme("batched_index", 0, 4 * 3600, q, stats=stats))
    print(f"   {dom} 404s: {rows} rows in {stats.batches} adaptive batches; plan: {stats.plan.describe()}")

    print("== 4. train the analytics LM on the event stream ==")
    cfg = get_config("llcysa-analytics-100m", smoke=True)
    tok = EventTokenizer(store, vocab_size=cfg.vocab_size)
    it = tok.sequences(0, 4 * 3600, seq_len=129, batch=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = OptConfig(lr=1e-3, warmup_steps=5, total_steps=30)
    state = adamw_init(params, opt_cfg)

    @jax.jit
    def step(p, s, b):
        (loss, _), grads = jax.value_and_grad(
            lambda pp: forward_train(pp, cfg, b, remat=False), has_aux=True
        )(p)
        p, s, _ = adamw_update(p, grads, s, opt_cfg)
        return p, s, loss

    losses = []
    for i in range(30):
        raw = next(it)
        params, state, loss = step(
            params, state, {"inputs": jnp.asarray(raw[:, :-1]), "targets": jnp.asarray(raw[:, 1:])}
        )
        losses.append(float(loss))
    print(f"   loss {losses[0]:.3f} -> {losses[-1]:.3f} over {len(losses)} steps")

    print("== 5. anomaly scoring: LM surprise per traffic window ==")

    @jax.jit
    def nll(p, b):
        return forward_train(p, cfg, b, remat=False)[0]

    scores = []
    for w0 in range(0, 4 * 3600, 3600):
        raw = next(tok.sequences(w0, w0 + 3600, seq_len=129, batch=2, seed=w0))
        s = float(nll(params, {"inputs": jnp.asarray(raw[:, :-1]), "targets": jnp.asarray(raw[:, 1:])}))
        scores.append((w0 // 3600, s))
    for h, s in scores:
        bar = "#" * int((s - min(x for _, x in scores)) * 40 + 1)
        print(f"   hour {h}: surprise {s:.3f} {bar}")
    print("pipeline complete.")


if __name__ == "__main__":
    main()
