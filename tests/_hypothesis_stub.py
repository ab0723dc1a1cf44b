"""Minimal, dependency-free stand-in for `hypothesis`.

The suite's property tests are written against the real hypothesis API
(`pip install -e .[test]` pulls it in — see pyproject.toml). Some execution
environments are hermetic: no network, no hypothesis wheel. Rather than
skip every property test there, conftest.py installs this shim into
`sys.modules['hypothesis']` when the real package is absent.

It implements exactly the API surface the suite uses — `given`, `settings`,
`example`, and the strategies `integers / floats / booleans / sampled_from / lists /
tuples / data` — as a deterministic random sweep: each decorated test runs
`max_examples` times with values drawn from a per-test seeded numpy
Generator. No shrinking, no database, no coverage-guided search; it is a
fuzz harness, not a replacement. The draw distributions are uniform, which
matches how the suite uses hypothesis (range/shape sweeps, not adversarial
edge-case mining).
"""
from __future__ import annotations

import functools
import inspect
import sys
import types
import zlib

import numpy as np

__version__ = "0.0-stub"

_DEFAULT_MAX_EXAMPLES = 20


class _Strategy:
    """Base class: a strategy is anything with draw(rng) -> value."""

    def draw(self, rng: np.random.Generator):  # pragma: no cover - abstract
        raise NotImplementedError


class _Integers(_Strategy):
    def __init__(self, min_value=None, max_value=None):
        self.lo = -(1 << 16) if min_value is None else int(min_value)
        self.hi = (1 << 16) if max_value is None else int(max_value)

    def draw(self, rng):
        return int(rng.integers(self.lo, self.hi + 1))


class _Floats(_Strategy):
    def __init__(self, min_value=None, max_value=None, **_kw):
        self.lo = -1e6 if min_value is None else float(min_value)
        self.hi = 1e6 if max_value is None else float(max_value)

    def draw(self, rng):
        return float(rng.uniform(self.lo, self.hi))


class _Booleans(_Strategy):
    def draw(self, rng):
        return bool(rng.integers(0, 2))


class _SampledFrom(_Strategy):
    def __init__(self, elements):
        self.elements = list(elements)

    def draw(self, rng):
        return self.elements[int(rng.integers(0, len(self.elements)))]


class _Lists(_Strategy):
    def __init__(self, elements, min_size=0, max_size=None, **_kw):
        self.elements = elements
        self.min_size = int(min_size)
        self.max_size = self.min_size + 10 if max_size is None else int(max_size)

    def draw(self, rng):
        n = int(rng.integers(self.min_size, self.max_size + 1))
        return [self.elements.draw(rng) for _ in range(n)]


class _Tuples(_Strategy):
    def __init__(self, *elements):
        self.elements = elements

    def draw(self, rng):
        return tuple(e.draw(rng) for e in self.elements)


class _DataObject:
    """Interactive draw handle (`@given(st.data())` style)."""

    def __init__(self, rng):
        self._rng = rng

    def draw(self, strategy, label=None):
        return strategy.draw(self._rng)


class _DataStrategy(_Strategy):
    def draw(self, rng):
        return _DataObject(rng)


def settings(max_examples: int = _DEFAULT_MAX_EXAMPLES, deadline=None, **_kw):
    """Decorator: records max_examples for the given() runner to pick up."""

    def deco(fn):
        fn._stub_max_examples = max_examples
        return fn

    return deco


def example(**kwargs):
    """Decorator: an explicit case the given() runner always runs first
    (real hypothesis keeps discovered failures this way). `.via()` is
    accepted and ignored."""

    def deco(fn):
        fn._stub_examples = [kwargs] + list(getattr(fn, "_stub_examples", []))
        return fn

    deco.via = lambda _why: deco
    return deco


def given(*pos_strategies, **kw_strategies):
    """Decorator: run the test max_examples times with drawn arguments.

    Positional strategies bind (like hypothesis) to the test's rightmost
    parameters; keyword strategies bind by name. Bound parameters are
    removed from the wrapper's visible signature so pytest still injects
    the remaining ones as fixtures.
    """

    def deco(fn):
        max_examples = getattr(fn, "_stub_max_examples", _DEFAULT_MAX_EXAMPLES)
        examples = getattr(fn, "_stub_examples", [])
        sig = inspect.signature(fn)
        params = list(sig.parameters.values())
        bound_names = set(kw_strategies)
        n_pos = len(pos_strategies)
        remaining = [p for p in params if p.name not in bound_names]
        pos_names = [p.name for p in remaining[len(remaining) - n_pos :]] if n_pos else []
        fixture_params = [
            p for p in remaining if p.name not in pos_names
        ]

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            seed = zlib.crc32(f"{fn.__module__}.{fn.__qualname__}".encode())
            rng = np.random.default_rng(seed)
            for ex in examples:
                fn(*args, **kwargs, **ex)
            for _ in range(max_examples):
                drawn = {name: s.draw(rng) for name, s in kw_strategies.items()}
                for name, s in zip(pos_names, pos_strategies):
                    drawn[name] = s.draw(rng)
                fn(*args, **kwargs, **drawn)

        wrapper.__signature__ = sig.replace(parameters=fixture_params)
        # pytest introspects __wrapped__ first; it must not resurrect the
        # strategy-bound parameters as fixtures.
        del wrapper.__wrapped__
        return wrapper

    return deco


def assume(condition) -> bool:
    """Degraded assume: a failed assumption just skips nothing (the sweep
    is random, not guided); returns the condition for manual guarding."""
    return bool(condition)


class HealthCheck:
    all = ()
    function_scoped_fixture = "function_scoped_fixture"
    too_slow = "too_slow"


def _strategies_module() -> types.ModuleType:
    st = types.ModuleType("hypothesis.strategies")
    st.integers = _Integers
    st.floats = _Floats
    st.booleans = _Booleans
    st.sampled_from = _SampledFrom
    st.lists = _Lists
    st.tuples = _Tuples
    st.data = _DataStrategy
    return st


def install() -> None:
    """Register this shim as `hypothesis` (+ `hypothesis.strategies`)."""
    mod = types.ModuleType("hypothesis")
    mod.given = given
    mod.settings = settings
    mod.example = example
    mod.assume = assume
    mod.HealthCheck = HealthCheck
    mod.__version__ = __version__
    mod.strategies = _strategies_module()
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = mod.strategies
