"""Vendored fallback for the `hypothesis` subset this suite uses.

When the real `hypothesis` package is unavailable, ``tests/conftest.py``
installs this module under ``sys.modules['hypothesis']`` so the
property-based test modules collect and run everywhere.  It is NOT a
hypothesis reimplementation: no shrinking, no example database, no
assume/filter machinery — just deterministic seeded-random sampling of
the strategy combinators the tests actually import (`given`, `settings`,
`example`, `strategies.integers/floats/lists/sampled_from/composite`).

Determinism: example i of test f draws from ``random.Random(hash((f
qualname, i)))`` so failures are reproducible run-to-run without any
state on disk.
"""
from __future__ import annotations

import functools
import inspect
import random
import types
import zlib

DEFAULT_MAX_EXAMPLES = 25


class Strategy:
    """A sampler: example(rng) -> value."""

    def __init__(self, sample):
        self._sample = sample

    def example(self, rng: random.Random):
        return self._sample(rng)


def integers(min_value: int, max_value: int) -> Strategy:
    return Strategy(lambda rng: rng.randint(min_value, max_value))


def floats(min_value: float, max_value: float, *, allow_nan: bool = False,
           allow_infinity: bool = False) -> Strategy:
    del allow_nan, allow_infinity  # bounded draws are always finite
    return Strategy(lambda rng: rng.uniform(min_value, max_value))


def booleans() -> Strategy:
    return Strategy(lambda rng: rng.random() < 0.5)


def sampled_from(elements) -> Strategy:
    elements = list(elements)
    if not elements:
        raise ValueError("sampled_from requires a non-empty sequence")
    return Strategy(lambda rng: rng.choice(elements))


def lists(elements: Strategy, *, min_size: int = 0, max_size: int = 10,
          unique: bool = False) -> Strategy:
    def sample(rng: random.Random):
        n = rng.randint(min_size, max_size)
        if not unique:
            return [elements.example(rng) for _ in range(n)]
        out, seen = [], set()
        # bounded retries: the sample space may be smaller than n
        for _ in range(100 * max(n, 1)):
            if len(out) >= n:
                break
            v = elements.example(rng)
            key = repr(v)
            if key not in seen:
                seen.add(key)
                out.append(v)
        if len(out) < min_size:
            raise ValueError("could not draw enough unique elements")
        return out

    return Strategy(sample)


def just(value) -> Strategy:
    return Strategy(lambda rng: value)


def composite(fn):
    """@st.composite def s(draw, **kw): ... -> s(**kw) is a Strategy."""
    @functools.wraps(fn)
    def builder(*args, **kwargs):
        def sample(rng: random.Random):
            return fn(lambda strat: strat.example(rng), *args, **kwargs)
        return Strategy(sample)
    return builder


class settings:  # noqa: N801 — mirrors hypothesis' lowercase decorator
    def __init__(self, max_examples: int = DEFAULT_MAX_EXAMPLES,
                 deadline=None, **_ignored):
        self.max_examples = max_examples
        self.deadline = deadline

    def __call__(self, fn):
        fn._compat_settings = self
        return fn


def example(*args, **kwargs):
    """An explicit case, run before the drawn ones."""
    def decorate(fn):
        fn._compat_examples = [(args, kwargs),
                               *getattr(fn, "_compat_examples", ())]
        return fn
    return decorate


def given(*strategies_args, **strategies_kw):
    def decorate(fn):
        cfg = getattr(fn, "_compat_settings", None)
        n = cfg.max_examples if cfg is not None else DEFAULT_MAX_EXAMPLES

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            for ex_args, ex_kw in getattr(fn, "_compat_examples", ()):
                fn(*args, *ex_args, **kwargs, **ex_kw)
            base = zlib.adler32(fn.__qualname__.encode())
            for i in range(n):
                rng = random.Random((base << 20) + i)
                drawn = [s.example(rng) for s in strategies_args]
                drawn_kw = {k: s.example(rng)
                            for k, s in strategies_kw.items()}
                try:
                    fn(*args, *drawn, **kwargs, **drawn_kw)
                except Exception as e:  # pragma: no cover - failure path
                    raise AssertionError(
                        f"falsifying example #{i} of {fn.__qualname__}: "
                        f"args={drawn!r} kwargs={drawn_kw!r}") from e

        # hide the drawn parameters from pytest's fixture resolution
        # (real hypothesis does the same); fixtures are unsupported here.
        del wrapper.__wrapped__
        wrapper.__signature__ = inspect.Signature()
        wrapper.hypothesis_compat = True
        return wrapper

    return decorate


# module object importable as `hypothesis.strategies`
strategies = types.ModuleType("hypothesis.strategies")
strategies.integers = integers
strategies.floats = floats
strategies.booleans = booleans
strategies.sampled_from = sampled_from
strategies.lists = lists
strategies.just = just
strategies.composite = composite
