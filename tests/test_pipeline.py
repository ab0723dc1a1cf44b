"""Ingest pipeline fault tolerance: drain, straggler re-queue, elastic
workers, shard-count guidance."""
import os
import tempfile

import numpy as np
import pytest

from repro.core import EventStore, web_proxy_schema
from repro.core.ingest import check_shard_guidance
from repro.pipeline import IngestWorkerPool, MasterIngestQueue, FileTask, SyntheticWebProxySource
from repro.pipeline.tokenizer import EventTokenizer


@pytest.fixture()
def staged_files(tmp_path):
    src = SyntheticWebProxySource(n_domains=100, seed=5)
    return src.write_files(str(tmp_path), n_files=6, lines_per_file=1500, t_start=0, t_stop=7200)


def test_pool_drains_all_files(staged_files):
    store = EventStore(web_proxy_schema(), n_shards=4)
    pool = IngestWorkerPool(store, n_workers=3)
    for p in staged_files:
        pool.submit_file(p)
    reports = pool.drain(timeout_s=120)
    assert store.total_rows == 6 * 1500
    assert sum(r.files for r in reports) == 6


def test_straggler_requeue(staged_files):
    """A worker that dies mid-lease must not lose its file: the lease
    expires and another worker re-ingests it."""
    store = EventStore(web_proxy_schema(), n_shards=4)
    # Timeout long enough that live workers always heartbeat in time (a
    # too-short lease would legitimately double-deliver: at-least-once).
    pool = IngestWorkerPool(store, n_workers=3, lease_timeout_s=2.0)
    pool.kill_worker(0)  # dies silently on its first claim
    for p in staged_files:
        pool.submit_file(p)
    pool.drain(timeout_s=120)
    assert store.total_rows == 6 * 1500  # nothing lost


def test_elastic_add_worker(staged_files):
    store = EventStore(web_proxy_schema(), n_shards=4)
    pool = IngestWorkerPool(store, n_workers=2)
    for p in staged_files:
        pool.submit_file(p)
    pool.add_worker()  # join mid-run
    pool.drain(timeout_s=120)
    assert store.total_rows == 6 * 1500


def test_lease_expiry_requeues():
    q = MasterIngestQueue(n_partitions=2, lease_timeout_s=0.05)
    q.submit(FileTask("/tmp/x", "web_proxy"))
    task = q.claim("w0", 0)
    assert task is not None and q.in_flight == 1
    import time

    time.sleep(0.1)
    assert q.expire_now() == 1
    assert q.pending == 1  # re-queued
    t2 = q.claim("w1", 1)  # work stealing across partitions
    assert t2 is not None and t2.attempts == 2


def test_shard_guidance_enforced():
    store = EventStore(web_proxy_schema(), n_shards=2)
    with pytest.raises(ValueError):
        IngestWorkerPool(store, n_workers=8)  # N=2 < 8/2
    assert check_shard_guidance(4, 8)
    assert not check_shard_guidance(3, 8)


def test_tokenizer_batches(staged_files):
    store = EventStore(web_proxy_schema(), n_shards=4)
    pool = IngestWorkerPool(store, n_workers=2)
    for p in staged_files:
        pool.submit_file(p)
    pool.drain(timeout_s=120)
    tok = EventTokenizer(store, vocab_size=8192)
    batch = next(tok.sequences(0, 7200, seq_len=64, batch=4))
    assert batch.shape == (4, 64)
    assert batch.dtype == np.int32
    assert batch.min() >= 0 and batch.max() < 8192


def test_gen_lines_spell_out_gen_codes():
    """One data model: the text lines are the coded events spelled out
    through the vocabulary, field for field, under the same seed."""
    from repro.pipeline.sources import FIELDS, parse_web_proxy_lines

    lines = SyntheticWebProxySource(n_domains=50, seed=3).gen_lines(400, 0, 3600)
    src = SyntheticWebProxySource(n_domains=50, seed=3)
    ts, codes = src.gen_codes(400, 0, 3600)
    vocab = src.vocabulary()
    got_ts, cols = parse_web_proxy_lines(lines)
    np.testing.assert_array_equal(got_ts, ts)
    for j, f in enumerate(FIELDS):
        assert cols[f] == vocab[f][codes[:, j]].tolist(), f
    assert (codes[:, FIELDS.index("domain")] == codes[:, FIELDS.index("referer")]).all()
