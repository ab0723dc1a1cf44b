"""Synthetic web-proxy log source — the paper's experimental data (§IV):
"web traffic captured from web proxy server log files. Each event
occurrence represents a single HTTP request and has dozens of attributes."

The generator emits raw text lines (tab-separated) so ingest workers do
real parsing work — the paper attributes the 1.1 MB/s-per-client ceiling to
client-side costs, so the reproduction must actually pay them. gen_codes
gives the same events as value codes into vocabulary(), for device-path
sizes where per-row Python would dominate.

Domain popularity follows a Zipf law, giving the paper's Query A/B/C
selectivity tiers (most popular / somewhat popular / unpopular domain).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

FIELDS = [
    "src_ip",
    "dst_ip",
    "domain",
    "url_path",
    "method",
    "status",
    "user_agent",
    "content_type",
    "bytes_out",
    "bytes_in",
    "referer",
    "scheme",
]

_METHODS = ["GET", "POST", "PUT", "HEAD"]
_METHOD_P = [0.78, 0.15, 0.02, 0.05]
_STATUS = ["200", "304", "404", "500", "302"]
_STATUS_P = [0.8, 0.08, 0.07, 0.02, 0.03]
_AGENTS = [f"agent/{i}.0" for i in range(12)]
_CTYPES = ["text/html", "application/json", "image/png", "text/css", "video/mp4"]


@dataclass
class SyntheticWebProxySource:
    n_domains: int = 2000
    zipf_a: float = 1.3
    seed: int = 7

    def __post_init__(self):
        self._rng = np.random.default_rng(self.seed)
        self._domains = np.asarray(
            [f"d{i:05d}.example.com" for i in range(self.n_domains)]
        )
        # Zipf popularity over a fixed domain universe.
        ranks = np.arange(1, self.n_domains + 1, dtype=np.float64)
        p = ranks ** (-self.zipf_a)
        self._p = p / p.sum()
        self._vocab = None  # vocabulary(), built on first use

    def domain_by_popularity(self, quantile: float) -> str:
        """Domain at a popularity quantile: 0.0 = most popular (the paper's
        Query A), ~0.5 = somewhat popular (B), ~0.99 = unpopular (C)."""
        idx = min(int(quantile * (self.n_domains - 1)), self.n_domains - 1)
        return str(self._domains[idx])

    def vocabulary(self) -> Dict[str, np.ndarray]:
        """Each field's value universe, as a numpy string array indexed by
        value code: gen_codes draws codes into it, gen_lines spells them
        out. Built once per source (bytes_in alone is ~1M strings)."""
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
        """n events as (ts int64 [n] sorted, int32 [n, len(FIELDS)] value
        codes into vocabulary(), columns in FIELDS order): Zipf domains
        (the referer is the same domain), weighted methods and statuses,
        every other field uniform over its universe."""
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

    def gen_lines(self, n: int, t_start: int, t_stop: int) -> List[str]:
        """n raw log lines with timestamps uniform in [t_start, t_stop]:
        gen_codes' events spelled out through vocabulary()."""
        ts, codes = self.gen_codes(n, t_start, t_stop)
        vocab = self.vocabulary()
        cols = [vocab[f][codes[:, j]].tolist() for j, f in enumerate(FIELDS)]
        return ["\t".join(row) for row in zip(ts.astype(str).tolist(), *cols)]

    def write_files(
        self, directory: str, n_files: int, lines_per_file: int, t_start: int, t_stop: int
    ) -> List[str]:
        """Stage files on the 'central filesystem' (paper §II)."""
        os.makedirs(directory, exist_ok=True)
        paths = []
        span = (t_stop - t_start) // max(n_files, 1)
        for i in range(n_files):
            p = os.path.join(directory, f"webproxy_{i:05d}.log")
            lo = t_start + i * span
            with open(p, "w") as f:
                f.write("\n".join(self.gen_lines(lines_per_file, lo, lo + span)) + "\n")
            paths.append(p)
        return paths


def parse_web_proxy_line(line: str) -> Tuple[int, Dict[str, str]]:
    """Parse one raw line -> (ts, field values). The real client-side work."""
    parts = line.rstrip("\n").split("\t")
    ts = int(parts[0])
    return ts, dict(zip(FIELDS, parts[1:]))


def parse_web_proxy_lines(
    lines: Sequence[str],
) -> Tuple[np.ndarray, Dict[str, List[str]]]:
    """Bulk parse -> (ts array, columnar field values)."""
    ts = np.empty(len(lines), dtype=np.int64)
    cols: Dict[str, List[str]] = {f: [] for f in FIELDS}
    for i, line in enumerate(lines):
        parts = line.rstrip("\n").split("\t")
        ts[i] = int(parts[0])
        for f, v in zip(FIELDS, parts[1:]):
            cols[f].append(v)
    return ts, cols
