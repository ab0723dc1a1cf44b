"""Frozen copy of the web-proxy event generator.

Copied from src/repro/pipeline/sources.py (SyntheticWebProxySource.
vocabulary and gen_codes) at commit a706375d2ec3ae046a9a10ad90b9f70ad495ba7d,
so that a later change to the program cannot move the benchmark's inputs.
Only the draw order and the distributions matter; both are kept exactly.

Events are value codes into vocabulary(): Zipf domains (the referer is
the same domain), weighted methods and statuses, every other field
uniform over its universe, timestamps uniform over [t_start, t_stop] and
sorted.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

FIELDS = (
    "src_ip", "dst_ip", "domain", "url_path", "method", "status",
    "user_agent", "content_type", "bytes_out", "bytes_in", "referer", "scheme",
)

_METHODS = ["GET", "POST", "PUT", "HEAD"]
_METHOD_P = [0.78, 0.15, 0.02, 0.05]
_STATUS = ["200", "304", "404", "500", "302"]
_STATUS_P = [0.8, 0.08, 0.07, 0.02, 0.03]
_AGENTS = [f"agent/{i}.0" for i in range(12)]
_CTYPES = ["text/html", "application/json", "image/png", "text/css", "video/mp4"]


class WebProxyGenerator:
    """Seeded source of web-proxy events as dictionary codes."""

    def __init__(self, seed: int, n_domains: int = 2000, zipf_a: float = 1.3):
        self.n_domains = n_domains
        self._rng = np.random.default_rng(seed)
        self._domains = np.asarray([f"d{i:05d}.example.com" for i in range(n_domains)])
        ranks = np.arange(1, n_domains + 1, dtype=np.float64)
        p = ranks ** (-zipf_a)
        self._p = p / p.sum()
        self._vocab = None

    def vocabulary(self) -> Dict[str, np.ndarray]:
        """Each field's value universe, indexed by value code."""
        if self._vocab is None:
            a = np.arange(1 << 16)
            mid = np.char.add(np.char.add((a >> 8).astype(str), "."), (a & 255).astype(str))
            self._vocab = {
                "src_ip": np.char.add(np.char.add("10.", mid), ".1"),
                "dst_ip": np.char.add(np.char.add("93.", mid), ".7"),
                "domain": self._domains,
                "url_path": np.char.add("/p/", np.arange(4000).astype(str)),
                "method": np.asarray(_METHODS),
                "status": np.asarray(_STATUS),
                "user_agent": np.asarray(_AGENTS),
                "content_type": np.asarray(_CTYPES),
                "bytes_out": np.arange(64, 4096).astype(str),
                "bytes_in": np.arange(128, 1 << 20).astype(str),
                "referer": np.char.add(np.char.add("https://", self._domains), "/r"),
                "scheme": np.asarray(["https"]),
            }
        return self._vocab

    def gen_codes(self, n: int, t_start: int, t_stop: int) -> Tuple[np.ndarray, np.ndarray]:
        """n events: (ts int64 [n] sorted, int32 [n, 12] codes in FIELDS order)."""
        rng = self._rng
        ts = np.sort(rng.integers(t_start, t_stop + 1, n))
        dom = rng.choice(self.n_domains, p=self._p, size=n)
        cols = {
            "domain": dom,
            "referer": dom,
            "method": rng.choice(len(_METHODS), size=n, p=_METHOD_P),
            "status": rng.choice(len(_STATUS), size=n, p=_STATUS_P),
        }
        for f, values in self.vocabulary().items():
            if f not in cols:
                cols[f] = rng.integers(0, len(values), n)
        return ts.astype(np.int64), np.stack([cols[f] for f in FIELDS], axis=1).astype(np.int32)
