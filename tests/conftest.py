"""Shared test configuration.

Drops JAX's compiled programs after every test
(``_release_compiled_programs``), and provides a deterministic fallback
shim for ``hypothesis`` when the real package is not installed (this
container ships without it).  Property
tests then degrade to a fixed sweep of seeded examples instead of
breaking collection for the whole file.  The shim covers exactly the
subset the suite uses: ``@settings(max_examples=..., deadline=...)``,
``@given(...)`` over positional strategies, and ``st.integers`` /
``st.binary`` / ``st.floats``.
"""
import random
import sys
import types
import zlib

import jax
import pytest


def _install_hypothesis_stub() -> None:
    mod = types.ModuleType("hypothesis")
    st = types.ModuleType("hypothesis.strategies")

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    def integers(min_value=0, max_value=2 ** 31 - 1):
        return _Strategy(lambda rng: rng.randint(min_value, max_value))

    def binary(min_size=0, max_size=64):
        def draw(rng):
            n = rng.randint(min_size, max_size)
            return bytes(rng.randrange(256) for _ in range(n))
        return _Strategy(draw)

    def floats(min_value=0.0, max_value=1.0, **_kw):
        return _Strategy(lambda rng: rng.uniform(min_value, max_value))

    st.integers, st.binary, st.floats = integers, binary, floats

    def settings(max_examples=10, **_kw):
        def deco(fn):
            fn._stub_max_examples = max_examples
            return fn
        return deco

    def given(*strategies):
        def deco(fn):
            # NOTE: no functools.wraps — pytest must see a zero-arg
            # signature, not the original one (drawn args would otherwise
            # be collected as fixtures).
            def runner():
                n = getattr(runner, "_stub_max_examples",
                            getattr(fn, "_stub_max_examples", 10))
                base = zlib.crc32(fn.__qualname__.encode())
                for i in range(n):
                    rng = random.Random(base + 0x9E3779B9 * i)
                    fn(*[s.draw(rng) for s in strategies])
            runner.__name__ = fn.__name__
            runner.__qualname__ = fn.__qualname__
            runner.__doc__ = fn.__doc__
            return runner
        return deco

    mod.given, mod.settings, mod.strategies = given, settings, st
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st


try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    _install_hypothesis_stub()


@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """Drop JAX's compiled programs after each test.  The CPU backend
    maps memory for every program it compiles and JAX's caches keep
    them alive, so a worker that runs a whole file of op-by-op serving
    references reaches the kernel's per-process map limit
    (``vm.max_map_count``, 65530 by default) and dies mid-file."""
    yield
    jax.clear_caches()
