"""Root pytest config.

When the real ``hypothesis`` package is unavailable (hermetic containers
where ``pip install`` is not an option), install a deterministic,
minimal stand-in into ``sys.modules`` before test collection so the
property-test modules still collect and run.  CI installs the real
package via ``pip install -e .[test]``; with it, this file only loads a
profile that derandomizes every property test and keeps no example
database, so each run draws the same examples and writes nothing.

The stub covers exactly the API surface the test-suite uses — ``given``,
``settings``, ``example``, ``assume`` and the ``integers`` / ``floats`` / ``booleans``
/ ``sampled_from`` / ``lists`` / ``tuples`` / ``just`` / ``one_of`` /
``permutations`` / ``sets`` / ``data`` / ``composite`` strategies —
drawing
pseudo-random examples from a per-test seeded RNG (reproducible across
runs; no shrinking, no example database).
"""

from __future__ import annotations

import functools
import inspect
import random
import sys
import types


def _build_hypothesis_stub() -> types.ModuleType:
    mod = types.ModuleType("hypothesis")
    st = types.ModuleType("hypothesis.strategies")
    mod.__version__ = "0.0-repro-stub"
    mod.strategies = st

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def draw(self, rnd: random.Random):
            return self._draw(rnd)

    def integers(min_value, max_value):
        def draw(rnd):
            if rnd.random() < 0.15:          # bias toward the bounds
                return rnd.choice((min_value, max_value))
            return rnd.randint(min_value, max_value)
        return _Strategy(draw)

    def floats(min_value, max_value, **_kw):
        def draw(rnd):
            if rnd.random() < 0.1:
                return rnd.choice((min_value, max_value))
            return rnd.uniform(min_value, max_value)
        return _Strategy(draw)

    def booleans():
        return _Strategy(lambda rnd: rnd.random() < 0.5)

    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda rnd: rnd.choice(elements))

    def lists(elements, *, min_size=0, max_size=10, **_kw):
        def draw(rnd):
            n = rnd.randint(min_size, max_size)
            return [elements.draw(rnd) for _ in range(n)]
        return _Strategy(draw)

    def tuples(*strategies):
        return _Strategy(lambda rnd: tuple(s.draw(rnd) for s in strategies))

    def just(value):
        return _Strategy(lambda rnd: value)

    def one_of(*strategies):
        flat = list(strategies)
        return _Strategy(lambda rnd: rnd.choice(flat).draw(rnd))

    def permutations(values):
        values = list(values)

        def draw(rnd):
            out = list(values)
            rnd.shuffle(out)
            return out
        return _Strategy(draw)

    def sets(elements, *, min_size=0, max_size=None, **_kw):
        def draw(rnd):
            n = rnd.randint(min_size, 10 if max_size is None else max_size)
            out = set()
            for _ in range(n * 5):       # bounded retry on duplicates
                if len(out) >= n:
                    break
                out.add(elements.draw(rnd))
            return out
        return _Strategy(draw)

    class _DataObject:
        """Interactive draws (``st.data()``): strategies drawn mid-test
        from the same per-test seeded RNG."""

        def __init__(self, rnd):
            self._rnd = rnd

        def draw(self, strategy, label=None):
            return strategy.draw(self._rnd)

    def data():
        return _Strategy(lambda rnd: _DataObject(rnd))

    def composite(fn):
        """``@st.composite``: the wrapped function's first argument
        becomes a ``draw`` callable bound to the per-test RNG."""
        @functools.wraps(fn)
        def builder(*args, **kwargs):
            def drawer(rnd):
                return fn(lambda strategy, label=None: strategy.draw(rnd),
                          *args, **kwargs)
            return _Strategy(drawer)
        return builder

    st.integers = integers
    st.floats = floats
    st.booleans = booleans
    st.sampled_from = sampled_from
    st.lists = lists
    st.tuples = tuples
    st.just = just
    st.one_of = one_of
    st.permutations = permutations
    st.sets = sets
    st.data = data
    st.composite = composite

    class _Unsatisfied(Exception):
        pass

    def assume(condition):
        if not condition:
            raise _Unsatisfied()
        return True

    class settings:  # noqa: N801 - mirrors the hypothesis name
        def __init__(self, max_examples=20, deadline=None, **_kw):
            self.max_examples = max_examples
            self.deadline = deadline

        def __call__(self, fn):
            fn._stub_settings = self
            return fn

    class HealthCheck:  # noqa: N801 - attribute access only
        all = staticmethod(lambda: [])
        too_slow = data_too_large = filter_too_much = None

    def example(**kwargs):
        """Pin one explicit example; ``given`` runs pinned examples
        before drawing any."""
        def decorate(fn):
            fn._stub_examples = [kwargs] + getattr(fn, "_stub_examples", [])
            return fn
        return decorate

    def given(*given_args, **given_kwargs):
        def decorate(fn):
            sig = inspect.signature(fn)
            names = list(sig.parameters)
            # positional strategies bind right-aligned, like hypothesis
            strat_map = dict(zip(names[len(names) - len(given_args):],
                                 given_args))
            strat_map.update(given_kwargs)
            passthrough = [sig.parameters[n] for n in names
                           if n not in strat_map]

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                cfg = (getattr(wrapper, "_stub_settings", None)
                       or getattr(fn, "_stub_settings", None))
                n_examples = cfg.max_examples if cfg else 20
                for pinned in getattr(fn, "_stub_examples", []):
                    fn(*args, **kwargs, **pinned)
                rnd = random.Random(f"{fn.__module__}.{fn.__qualname__}")
                ran = 0
                for _ in range(n_examples * 5):
                    if ran >= n_examples:
                        break
                    drawn = {k: s.draw(rnd) for k, s in strat_map.items()}
                    try:
                        fn(*args, **kwargs, **drawn)
                    except _Unsatisfied:
                        continue
                    ran += 1

            # pytest must only see the fixture params, not the drawn ones
            wrapper.__signature__ = sig.replace(parameters=passthrough)
            wrapper.hypothesis = types.SimpleNamespace(inner_test=fn)
            return wrapper
        return decorate

    mod.given = given
    mod.example = example
    mod.settings = settings
    mod.assume = assume
    mod.HealthCheck = HealthCheck
    mod.strategies = st
    return mod


try:
    import hypothesis
except ImportError:
    _stub = _build_hypothesis_stub()
    sys.modules["hypothesis"] = _stub
    sys.modules["hypothesis.strategies"] = _stub.strategies
else:
    hypothesis.settings.register_profile(
        "repro", derandomize=True, database=None, deadline=None)
    hypothesis.settings.load_profile("repro")
