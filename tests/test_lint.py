"""graftlint: per-checker unit tests on synthetic sources, plus the repo
gate — the whole linted surface must carry zero non-baselined findings (and
no stale baseline entries), so any new PRNG-reuse / retrace / host-sync /
donation / axis-name / dtype hazard fails the fast tier at the moment it is
introduced."""

import textwrap

from evotorch_tpu.analysis import (
    apply_baseline,
    default_baseline_path,
    lint_sources,
    load_baseline,
    run_lint,
)


def _lint(src, path="mod.py", checkers=None, extra=None):
    sources = {path: textwrap.dedent(src)}
    if extra:
        sources.update({k: textwrap.dedent(v) for k, v in extra.items()})
    return lint_sources(sources, checkers=checkers)


def _checkers(findings):
    return [f.checker for f in findings]


# ---------------------------------------------------------------------------
# prng
# ---------------------------------------------------------------------------


def test_prng_flags_double_consumption():
    findings = _lint(
        """
        import jax

        def f(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
        """,
        checkers=["prng"],
    )
    assert _checkers(findings) == ["prng"]
    assert "key" in findings[0].message


def test_prng_flags_loop_reuse():
    findings = _lint(
        """
        import jax

        def f(key):
            out = []
            for i in range(4):
                out.append(jax.random.normal(key, (3,)))
            return out
        """,
        checkers=["prng"],
    )
    assert _checkers(findings) == ["prng"]
    assert "loop" in findings[0].message


def test_prng_accepts_fresh_key_per_loop_iteration():
    # `for k in split(key, n)` hands a NEW key to every iteration — the
    # canonical batching idiom must not read as cross-iteration reuse
    findings = _lint(
        """
        import jax

        def f(key):
            out = []
            for k in jax.random.split(key, 4):
                out.append(jax.random.normal(k, (3,)))
            return out
        """,
        checkers=["prng"],
    )
    assert findings == []


def test_prng_accepts_split_discipline():
    findings = _lint(
        """
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (3,))
            b = jax.random.uniform(k2, (3,))
            return a + b

        def g(key):
            for i in range(4):
                key, sub = jax.random.split(key)
                yield jax.random.normal(sub, (3,))

        def h(key, interpret):
            # mutually exclusive paths may both consume the same key
            if interpret:
                return jax.random.normal(key, (2,))
            return jax.random.uniform(key, (2,))
        """,
        checkers=["prng"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# retrace
# ---------------------------------------------------------------------------


def test_retrace_flags_jit_in_loop_and_fresh_callees():
    findings = _lint(
        """
        import jax

        def bench(env):
            for n in (1, 2, 3):
                f = jax.jit(lambda x: x * n)    # jit-in-loop
                f(n)

        def harness(env, x):
            step = jax.jit(env.batch_step)      # fresh bound method
            fwd = jax.jit(lambda a: a + 1)      # fresh lambda
            return step(x), fwd(x)
        """,
        checkers=["retrace"],
    )
    details = sorted(f.detail for f in findings)
    assert details == [
        "jit-fresh-callee:env.batch_step",
        "jit-fresh-callee:lambda",
        "jit-in-loop",
    ]


def test_retrace_accepts_cached_builders_and_module_scope():
    findings = _lint(
        """
        import functools
        import jax

        _CACHE = {}

        def get(env):
            fn = _CACHE.get(env)
            if fn is None:
                fn = jax.jit(env.batch_step)
                _CACHE[env] = fn
            return fn

        @functools.lru_cache(maxsize=8)
        def build(env):
            return jax.jit(lambda s, a: env.step(s, a))

        top = jax.jit(lambda x: x + 1)  # module scope: built once per import

        def warm(envs):
            # cache-filling warm-up loop: one jit per cache key, not per call
            for env in envs:
                _CACHE[env] = jax.jit(env.batch_step)
        """,
        checkers=["retrace"],
    )
    assert findings == []


def test_retrace_cache_exemption_matches_real_memoizers_only():
    # a decorator merely NAMED like a cache does not memoize: the fresh
    # bound-method jit under it must still be reported
    findings = _lint(
        """
        import jax

        def clear_cache(fn):
            return fn

        @clear_cache
        def harness(env, x):
            step = jax.jit(env.batch_step)
            return step(x)
        """,
        checkers=["retrace"],
    )
    assert [f.detail for f in findings] == ["jit-fresh-callee:env.batch_step"]


def test_retrace_flags_fstring_args_to_jitted_callable():
    findings = _lint(
        """
        import jax

        run = jax.jit(lambda tag, x: x)

        def f(x, i):
            return run(f"step{i}", x)
        """,
        checkers=["retrace"],
    )
    assert [f.detail for f in findings] == ["str-arg:run"]


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------


def test_host_sync_flags_traced_conversions():
    findings = _lint(
        """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return float(x) * 2

        @jax.jit
        def g(x):
            return np.asarray(x).sum()

        def h(x):
            return jax.lax.while_loop(lambda c: c[0].item() < 3, body, x)

        def body(c):
            return c
        """,
        checkers=["host-sync"],
    )
    details = sorted(f.detail for f in findings)
    assert details == ["float-in-trace", "item", "np-asarray"]


def test_host_sync_exempts_static_args_and_shapes():
    findings = _lint(
        """
        from functools import partial

        import jax

        @partial(jax.jit, static_argnames=("n", "mode"))
        def f(x, n, mode):
            k = int(n) + len(mode)
            m = int(x.shape[0])
            return x[: k + m]
        """,
        checkers=["host-sync"],
    )
    assert findings == []


def test_host_sync_flags_per_iteration_device_sync_in_host_loop():
    helper = """
        import jax.numpy as jnp

        def bonus(t, schedule):
            return jnp.where(t >= schedule[0], schedule[1], 0.0)
        """
    findings = _lint(
        """
        from helper import bonus

        def rollout(schedule):
            total = 0.0
            for t in range(100):
                total += float(bonus(t, schedule))
            return total
        """,
        checkers=["host-sync"],
        extra={"helper.py": helper},
    )
    assert [f.detail for f in findings] == ["loop-sync:bonus"]


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------


def test_donation_flags_undonated_state_steps():
    findings = _lint(
        """
        import jax

        def tell(state, values, evals):
            return state

        @jax.jit
        def step(state, key):
            return state

        def main():
            tell_jit = jax.jit(tell)
            return tell_jit
        """,
        checkers=["donation"],
    )
    details = sorted(f.detail for f in findings)
    assert details == ["undonated-state:step", "undonated-state:tell"]


def test_donation_resolves_cross_module_aliases():
    algo = """
        def pgpe_tell(state, values, evals):
            return state
        """
    findings = _lint(
        """
        import jax

        from algo import pgpe_tell

        def main(lowrank):
            if lowrank:
                tell = pgpe_tell
            else:
                tell = pgpe_tell
            tell_jit = jax.jit(tell)
            return tell_jit

        def chained():
            a = b = pgpe_tell  # chained alias: both names must resolve
            return jax.jit(b)
        """,
        checkers=["donation"],
        extra={"algo.py": algo},
    )
    assert sorted(f.detail for f in findings) == [
        "undonated-state:b",
        "undonated-state:tell",
    ]


def test_donation_accepts_donated_or_non_state_firsts():
    findings = _lint(
        """
        from functools import partial

        import jax

        def tell(state, values):
            return state

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, key):
            return state

        @jax.jit
        def evaluate(values, key):
            return values

        def main():
            return jax.jit(tell, donate_argnums=(0,))
        """,
        checkers=["donation"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# axis-name
# ---------------------------------------------------------------------------


def test_axis_name_flags_undeclared_literals():
    findings = _lint(
        """
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), axis_names=("pop",))

        def local(x):
            good = jax.lax.pmean(x, "pop")
            bad = jax.lax.psum(x, "popp")
            spec = P("batch")
            return good + bad, spec
        """,
        checkers=["axis-name"],
    )
    details = sorted(f.detail for f in findings)
    assert details == ["unknown-axis:batch", "unknown-axis:popp"]


def test_axis_name_collects_defaults_and_make_mesh():
    findings = _lint(
        """
        import jax

        def make_mesh(shape):
            ...

        def helper(x, axis_name="pop"):
            return jax.lax.pmean(x, axis_name)

        def entry(x):
            mesh = make_mesh({"pop": 4, "model": 2})
            return jax.lax.pmean(jax.lax.psum(x, "model"), "pop")
        """,
        checkers=["axis-name"],
    )
    assert findings == []


# ---------------------------------------------------------------------------
# dtype
# ---------------------------------------------------------------------------


def test_dtype_flags_x64_references():
    findings = _lint(
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        BAD = jnp.float64

        def f(x):
            return jnp.asarray(x, dtype="float64")

        @jax.jit
        def g(x):
            return x * np.float64(2.0)

        def host():
            return np.float64(1.0)  # host-side: allowed
        """,
        checkers=["dtype"],
    )
    details = sorted(f.detail for f in findings)
    assert details == ["dtype-str:float64", "np-x64:float64", "x64:float64"]


def test_dtype_flags_enable_x64():
    findings = _lint(
        """
        import jax

        jax.config.update("jax_enable_x64", True)
        """,
        checkers=["dtype"],
    )
    assert [f.detail for f in findings] == ["enable-x64"]


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------


def test_timing_flags_unsynced_measurement_of_jitted_call():
    findings = _lint(
        """
        import time

        import jax

        step = jax.jit(lambda s: s + 1)

        def bench(state):
            t0 = time.perf_counter()
            for _ in range(10):
                state = step(state)
            return time.perf_counter() - t0
        """,
        checkers=["timing"],
    )
    assert [f.detail for f in findings] == ["unsynced-timing:step"]
    assert "block_until_ready" in findings[0].message


def test_timing_accepts_block_until_ready_in_region():
    findings = _lint(
        """
        import time

        import jax

        step = jax.jit(lambda s: s + 1)

        def bench(state):
            t0 = time.perf_counter()
            for _ in range(10):
                state = step(state)
            jax.block_until_ready(state)
            return time.perf_counter() - t0
        """,
        checkers=["timing"],
    )
    assert findings == []


def test_timing_accepts_blocking_local_helper():
    # the `once()` pattern: the dispatch + block
    # live inside a locally-defined helper the timed loop calls
    findings = _lint(
        """
        import time

        import jax

        step = jax.jit(lambda s: s + 1)

        def bench(state):
            def once(s):
                out = step(s)
                jax.block_until_ready(out)
                return out

            once(state)
            t0 = time.perf_counter()
            for _ in range(10):
                state = once(state)
            return time.perf_counter() - t0
        """,
        checkers=["timing"],
    )
    assert findings == []


def test_timing_ignores_nested_def_merely_defined_in_region():
    # a helper DEFINED between the clock reads neither dispatches nor syncs:
    # its body's jitted call must not create a finding, and a
    # block_until_ready inside it must not excuse one
    findings = _lint(
        """
        import time

        import jax

        step = jax.jit(lambda s: s + 1)

        def defines_but_never_calls():
            t0 = time.perf_counter()
            def helper(s):
                return step(s)
            total = sum(range(100))
            return time.perf_counter() - t0, helper, total

        def dead_block_does_not_excuse(state):
            t0 = time.perf_counter()
            def never_called(s):
                jax.block_until_ready(s)
            state = step(state)
            return state, time.perf_counter() - t0
        """,
        checkers=["timing"],
    )
    assert [f.detail for f in findings] == ["unsynced-timing:step"]
    assert findings[0].symbol == "dead_block_does_not_excuse"


def test_timing_flags_unsynced_helper_called_in_region():
    # a called local helper contributes what its body does: jitted dispatch
    # without a block inside -> the region is an unsynced measurement
    findings = _lint(
        """
        import time

        import jax

        step = jax.jit(lambda s: s + 1)

        def bench(state):
            def once(s):
                return step(s)

            t0 = time.perf_counter()
            for _ in range(10):
                state = once(state)
            return time.perf_counter() - t0
        """,
        checkers=["timing"],
    )
    assert [f.detail for f in findings] == ["unsynced-timing:once"]


def test_timing_ignores_host_only_timing_and_jit_decorated_defs():
    findings = _lint(
        """
        import time

        import jax

        @jax.jit
        def step(state):
            return state + 1

        def host_bench():
            t0 = time.perf_counter()
            total = sum(range(100))
            return time.perf_counter() - t0, total

        def device_bench(state):
            t0 = time.perf_counter()
            state = step(state)
            dt = time.perf_counter() - t0
            return state, dt
        """,
        checkers=["timing"],
    )
    # host_bench times no jitted call; device_bench times the @jax.jit def
    # without blocking -> exactly one finding
    assert [f.detail for f in findings] == ["unsynced-timing:step"]
    assert findings[0].symbol == "device_bench"


# ---------------------------------------------------------------------------
# swallow
# ---------------------------------------------------------------------------


def test_swallow_flags_silent_broad_handlers():
    findings = _lint(
        """
        def f():
            try:
                risky()
            except:
                pass

        def g():
            try:
                risky()
            except Exception:
                x = 1
        """,
        checkers=["swallow"],
    )
    details = sorted(f.detail for f in findings)
    assert details == ["swallow:bare except", "swallow:except Exception"]


def test_swallow_accepts_reported_or_narrow_handlers():
    findings = _lint(
        """
        import logging
        import traceback

        from evotorch_tpu.observability.registry import counters

        log = logging.getLogger(__name__)

        def logged():
            try:
                risky()
            except Exception:
                log.warning("risky failed")

        def counted():
            try:
                risky()
            except Exception:
                counters.increment("risky.failures")

        def reraised():
            try:
                risky()
            except Exception:
                cleanup()
                raise

        def captured():
            try:
                risky()
            except Exception:
                tb = traceback.format_exc()
                record(tb)

        def narrow():
            try:
                risky()
            except (KeyError, OSError):
                pass
        """,
        checkers=["swallow"],
    )
    assert findings == []


def test_swallow_allow_comment_suppresses_with_reason():
    silent = """
        def f():
            try:
                risky()
            except Exception:  # graftlint: allow(swallow): teardown is best-effort
                pass
        """
    assert _lint(silent, checkers=["swallow"]) == []
    reasonless = """
        def f():
            try:
                risky()
            except Exception:  # graftlint: allow(swallow)
                pass
        """
    findings = _lint(reasonless, checkers=["swallow"])
    details = sorted(f.detail for f in findings)
    # the reasonless allow does NOT suppress, and is itself a finding
    assert details == ["missing-reason", "swallow:except Exception"]


# ---------------------------------------------------------------------------
# scoped allow-comments
# ---------------------------------------------------------------------------


def test_scoped_allow_suppresses_named_checker_only():
    src = """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            # graftlint: allow(host-sync): swap-point sync is this test's point
            a = np.asarray(x).sum()
            b = float(x)  # not covered by the allow above
            return a + b
        """
    findings = _lint(src, checkers=["host-sync"])
    assert [f.detail for f in findings] == ["float-in-trace"]


def test_scoped_allow_trailing_same_line():
    src = """
        import jax

        @jax.jit
        def f(x):
            return float(x)  # graftlint: allow(host-sync): intentional demo
        """
    assert _lint(src, checkers=["host-sync"]) == []


def test_scoped_allow_requires_reason():
    src = """
        import jax

        @jax.jit
        def f(x):
            return float(x)  # graftlint: allow(host-sync)
        """
    findings = _lint(src, checkers=["host-sync"])
    details = sorted(f.detail for f in findings)
    # the reasonless allow does NOT suppress, and is itself a finding
    assert details == ["float-in-trace", "missing-reason"]


def test_scoped_allow_trailing_does_not_cover_next_line():
    # a trailing allow excuses its own line ONLY: the adjacent violation
    # below it must still be reported
    src = """
        import jax

        @jax.jit
        def f(a, b):
            x = float(a)  # graftlint: allow(host-sync): intentional demo
            y = float(b)
            return x + y
        """
    findings = _lint(src, checkers=["host-sync"])
    assert [f.detail for f in findings] == ["float-in-trace"]
    assert findings[0].line == 7  # the uncovered second float()


def test_scoped_allow_inside_string_literal_is_inert():
    # allow-syntax in a string is data, not a directive: it must neither
    # suppress findings nor be reported as a reasonless allow
    src = """
        import jax

        HELP = "# graftlint: allow(host-sync)"

        @jax.jit
        def f(x):
            doc = "# graftlint: allow(host-sync): not a comment"
            return float(x), doc
        """
    findings = _lint(src, checkers=["host-sync"])
    assert [f.detail for f in findings] == ["float-in-trace"]


def test_scoped_allow_multiple_checkers():
    src = """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            # graftlint: allow(host-sync, dtype): x64 host pull is deliberate here
            return np.asarray(x) * np.float64(2.0)
        """
    assert _lint(src, checkers=["host-sync", "dtype"]) == []


# ---------------------------------------------------------------------------
# the repo gate
# ---------------------------------------------------------------------------


def test_repo_is_clean_modulo_baseline():
    """The acceptance gate: zero non-baselined findings on the whole linted
    surface, and no stale baseline entries (fixed findings must drop their
    grandfathering in the same change)."""
    findings = run_lint()
    baseline = load_baseline(default_baseline_path())
    new, stale = apply_baseline(findings, baseline)
    assert new == [], "non-baselined graftlint findings:\n" + "\n".join(
        f.format() for f in new
    )
    assert stale == [], "stale baseline entries (remove them):\n" + "\n".join(
        e["signature"] for e in stale
    )


def test_library_names_no_bench_variable():
    """The library takes its shapes from arguments: no file under
    ``evotorch_tpu/`` reads or names a variable of the deleted bench scripts
    (the benchmark is ``BENCHMARK.json`` + ``benchmark/``, which the library
    does not know)."""
    import pathlib
    import re

    import evotorch_tpu

    package = pathlib.Path(evotorch_tpu.__file__).parent
    named = re.compile("BENCH" + r"_[A-Z]")
    hits = [
        f"{path.relative_to(package)}:{number}: {line.strip()}"
        for path in sorted(package.rglob("*"))
        if path.suffix in (".py", ".json", ".md")
        for number, line in enumerate(path.read_text().splitlines(), 1)
        if named.search(line)
    ]
    assert hits == [], "\n".join(hits)


def test_baseline_is_multiset_matched():
    findings = _lint(
        """
        import jax

        def f(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            c = jax.random.gumbel(key, (3,))
            return a + b + c
        """,
        checkers=["prng"],
    )
    assert len(findings) == 2  # second and third consumption
    one_entry = [{"signature": findings[0].signature}]
    new, stale = apply_baseline(findings, one_entry)
    assert len(new) == 1 and stale == []
