"""Per-rule fixture suite for replint (``repro.analysis.lint``).

Each rule gets true-positive snippets it must flag and false-positive
snippets it must stay silent on — including the acceptance fixtures
from ISSUE 10: a seeded lock-order inversion the cycle detector must
flag and a correctly-ordered twin it must not.  Plus: suppression and
baseline round-trips, JSON reporter schema checks, and the tier-1
self-lint gate (the whole repo must lint clean with an empty baseline).

Fixture code lives in strings and is written to tmp_path, never
imported — replint is AST-only, so the snippets don't need runnable
imports (``pl.pallas_call`` etc. are never executed).
"""
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.lint import (
    REGISTRY,
    load_baseline,
    render_human,
    render_json,
    run_lint,
    split_baselined,
    write_baseline,
)

REPO = pathlib.Path(__file__).resolve().parent.parent


def lint_src(tmp_path, relname, code, select=None):
    p = tmp_path / relname
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    return run_lint([tmp_path], select=select, root=tmp_path)


def rule_ids(result):
    return sorted({f.rule for f in result.findings})


# ------------------------------------------------------------ registry
def test_registry_has_all_issue_rules():
    assert {
        "wall-clock",
        "swallowed-exception",
        "lock-discipline",
        "lock-order",
        "thread-lifecycle",
        "pallas-hygiene",
        "suppression",
    } <= set(REGISTRY)


def test_unknown_select_raises():
    with pytest.raises(ValueError, match="no-such-rule"):
        run_lint([REPO / "src" / "repro" / "launch" / "mesh.py"], select=["no-such-rule"])


# ----------------------------------------------------------- wall-clock
WALL_BAD = """
    import time
    from datetime import datetime

    def measure():
        t0 = time.time()
        stamp = datetime.now()
        return t0, stamp
"""


def test_wall_clock_flags_timing_paths(tmp_path):
    r = lint_src(tmp_path, "serving/mod.py", WALL_BAD, select=["wall-clock"])
    assert [f.symbol for f in r.findings] == [
        "time.time",
        "datetime.datetime.now",
    ]
    assert all(f.rule == "wall-clock" for f in r.findings)


def test_wall_clock_sees_through_import_alias(tmp_path):
    r = lint_src(
        tmp_path,
        "launch/mod.py",
        """
        from time import time as wall

        def f():
            return wall()
        """,
        select=["wall-clock"],
    )
    assert len(r.findings) == 1 and r.findings[0].symbol == "time.time"


def test_wall_clock_ignores_out_of_scope_and_monotonic(tmp_path):
    # same offending code OUTSIDE a timing path: silent
    assert not lint_src(
        tmp_path, "core/other.py", WALL_BAD, select=["wall-clock"]
    ).findings
    # monotonic sources and string/comment mentions in scope: silent
    assert not lint_src(
        tmp_path,
        "serving/ok.py",
        """
        import time

        BANNER = "never call time.time() here"

        def f():  # time.time() would be wrong
            return time.monotonic() + time.perf_counter()
        """,
        select=["wall-clock"],
    ).findings


def test_wall_clock_covers_simulator_file(tmp_path):
    r = lint_src(
        tmp_path, "core/simulator.py",
        "import time\n\nT0 = time.time()\n", select=["wall-clock"],
    )
    assert len(r.findings) == 1


# -------------------------------------------------- swallowed-exception
def test_swallowed_flags_silent_broad_catches(tmp_path):
    r = lint_src(
        tmp_path,
        "mod.py",
        """
        def silent_pass():
            try:
                work()
            except Exception:
                pass

        def bare_pass():
            try:
                work()
            except:
                pass

        def base_log_only(logger):
            try:
                work()
            except BaseException:
                logger.exception("boom")
        """,
        select=["swallowed-exception"],
    )
    assert len(r.findings) == 3
    kinds = sorted(f.symbol for f in r.findings)
    assert kinds == [
        "base:bare_pass",
        "base:base_log_only",
        "exception:silent_pass",
    ]


def test_swallowed_accepts_handled_broad_catches(tmp_path):
    r = lint_src(
        tmp_path,
        "mod.py",
        """
        def narrow():
            try:
                work()
            except ValueError:
                pass  # narrow: the author names what is absorbed

        def logged(logger):
            try:
                work()
            except Exception:
                logger.warning("fell back")

        def captured(self):
            try:
                work()
            except Exception as e:
                self.err = e

        def reraised():
            try:
                work()
            except BaseException:
                raise

        def error_channel(errors):
            try:
                work()
            except BaseException as e:
                errors.append(e)

        def sibling_interrupt(logger):
            try:
                work()
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException:
                logger.exception("rollback failed; original re-raised")
        """,
        select=["swallowed-exception"],
    )
    assert not r.findings


# ------------------------------------------------------ lock-discipline
def test_lock_discipline_flags_inconsistent_guard(tmp_path):
    r = lint_src(
        tmp_path,
        "mod.py",
        """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0  # bare in __init__ is fine: happens-before

            def inc(self):
                with self._lock:
                    self.n += 1

            def reset(self):
                self.n = 0  # RACE: bare write to a guarded attribute
        """,
        select=["lock-discipline"],
    )
    assert len(r.findings) == 1
    f = r.findings[0]
    assert f.symbol == "Counter.n" and "reset" in f.message


def test_lock_discipline_closure_resets_held_set(tmp_path):
    # a worker closure DEFINED inside `with lock` RUNS without it
    r = lint_src(
        tmp_path,
        "mod.py",
        """
        import threading

        class Spawner:
            def __init__(self):
                self._lock = threading.Lock()
                self.state = None

            def guarded(self):
                with self._lock:
                    self.state = "a"

            def spawn(self):
                with self._lock:
                    def worker():
                        self.state = "b"  # runs later, lock NOT held
                    return worker
        """,
        select=["lock-discipline"],
    )
    assert len(r.findings) == 1 and r.findings[0].symbol == "Spawner.state"


def test_lock_discipline_consistent_classes_are_clean(tmp_path):
    r = lint_src(
        tmp_path,
        "mod.py",
        """
        import threading

        class AlwaysGuarded:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def a(self):
                with self._lock:
                    self.n += 1

            def b(self):
                with self._lock:
                    self.n = 0

        class NoLocks:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
        """,
        select=["lock-discipline"],
    )
    assert not r.findings


# ----------------------------------------------------------- lock-order
INVERTED = """
    import threading

    class Inverted:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def forward(self):
            with self._a:
                with self._b:
                    pass

        def backward(self):
            with self._b:
                with self._a:
                    pass
"""

ORDERED = """
    import threading

    class Ordered:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def one(self):
            with self._a:
                with self._b:
                    pass

        def two(self):
            with self._a:
                with self._b:
                    with self._a:  # re-entry of a held lock: no ordering
                        pass
"""


def test_lock_order_flags_seeded_inversion(tmp_path):
    """ISSUE 10 acceptance fixture: the seeded inversion must be flagged."""
    r = lint_src(tmp_path, "mod.py", INVERTED, select=["lock-order"])
    assert len(r.findings) == 1
    f = r.findings[0]
    assert f.symbol == "Inverted:_a<_b" and "deadlock" in f.message


def test_lock_order_correctly_ordered_is_clean(tmp_path):
    """ISSUE 10 acceptance fixture: consistent order must pass clean."""
    r = lint_src(tmp_path, "mod.py", ORDERED, select=["lock-order"])
    assert not r.findings


def test_lock_order_cross_method_cycle_via_self_calls(tmp_path):
    # the inversion only exists through the call graph:
    # hold a -> helper takes b; hold b -> other helper takes a
    r = lint_src(
        tmp_path,
        "mod.py",
        """
        import threading

        class CrossMethod:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def path1(self):
                with self._a:
                    self._take_b()

            def _take_b(self):
                with self._b:
                    pass

            def path2(self):
                with self._b:
                    self._take_a()

            def _take_a(self):
                with self._a:
                    pass
        """,
        select=["lock-order"],
    )
    assert len(r.findings) == 1 and r.findings[0].symbol == "CrossMethod:_a<_b"


# ------------------------------------------------------ thread-lifecycle
def test_thread_lifecycle_flags_leaks(tmp_path):
    r = lint_src(
        tmp_path,
        "mod.py",
        """
        import threading

        def leak_named():
            t = threading.Thread(target=print)
            t.start()

        def leak_anonymous():
            threading.Thread(target=print).start()
        """,
        select=["thread-lifecycle"],
    )
    assert sorted(f.symbol for f in r.findings) == [
        "thread:leak_anonymous",
        "thread:leak_named",
    ]


def test_thread_lifecycle_accepts_each_lifecycle(tmp_path):
    r = lint_src(
        tmp_path,
        "mod.py",
        """
        import threading

        def daemonized():
            threading.Thread(target=print, daemon=True).start()

        def daemon_after():
            t = threading.Thread(target=print)
            t.daemon = True
            t.start()

        def joined():
            t = threading.Thread(target=print)
            t.start()
            t.join()

        def fleet():
            ts = [threading.Thread(target=print) for _ in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

        class Monitor:
            def start(self):
                self._thread = threading.Thread(target=print)
                self._thread.start()

            def stop(self):
                self._thread.join(timeout=5)
        """,
        select=["thread-lifecycle"],
    )
    assert not r.findings


# ------------------------------------------------------- pallas-hygiene
def test_pallas_hygiene_flags_bad_sites(tmp_path):
    r = lint_src(
        tmp_path,
        "kern.py",
        """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def hardcoded(x):
            return pl.pallas_call(kern, grid=(4,), interpret=True)(x)

        def missing(x):
            return pl.pallas_call(kern, grid=(4,))(x)

        def dynamic_grid(x, interpret):
            return pl.pallas_call(
                kern, grid=(jnp.ceil(4),), interpret=interpret,
            )(x)

        def unrouted_local(x):
            flag = bool(x)
            return pl.pallas_call(kern, grid=(4,), interpret=flag)(x)
        """,
        select=["pallas-hygiene"],
    )
    symbols = sorted(f.symbol for f in r.findings)
    assert symbols == [
        "grid-dynamic:dynamic_grid",
        "interpret-hardcoded:hardcoded",
        "interpret-missing:missing",
        "interpret-unrouted:dynamic_grid",  # no default_interpret import
        "interpret-unrouted:unrouted_local",
    ]


def test_pallas_hygiene_accepts_routed_sites(tmp_path):
    r = lint_src(
        tmp_path,
        "kern.py",
        """
        from jax.experimental import pallas as pl
        from repro.kernels.config import default_interpret

        def resolved_local(x, interpret=None):
            interpret = default_interpret(interpret)
            return pl.pallas_call(
                kern,
                grid=(x.shape[0], pl.cdiv(x.shape[1], 128)),
                in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
                interpret=interpret,
            )(x)

        def _impl(x, interpret):
            # private-impl pattern: the public wrapper resolved it
            return pl.pallas_call(kern, grid=(4,), interpret=interpret)(x)

        def at_call_site(x):
            return pl.pallas_call(
                kern, grid=(4,), interpret=default_interpret(None),
            )(x)
        """,
        select=["pallas-hygiene"],
    )
    assert not r.findings


def test_pallas_hygiene_flags_dynamic_block_shape(tmp_path):
    r = lint_src(
        tmp_path,
        "kern.py",
        """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from repro.kernels.config import default_interpret

        def bad_block(x):
            return pl.pallas_call(
                kern,
                grid=(4,),
                in_specs=[pl.BlockSpec((jnp.size(x), 128), lambda i: (i, 0))],
                interpret=default_interpret(None),
            )(x)
        """,
        select=["pallas-hygiene"],
    )
    assert [f.symbol for f in r.findings] == ["block-dynamic:bad_block"]


# --------------------------------------------------------- suppressions
# Built by concatenation so this test file's own source never contains a
# literal replint marker — the suppression parser is line-based (it must
# be: it reads comments), and the repo self-lint covers this file too.
_DISABLE = "# " + "replint: disable="

SUPPRESSED_INLINE = f"""
    import time

    def f():
        return time.time()  {_DISABLE}wall-clock -- fixture: wall time IS the payload here
"""

SUPPRESSED_ABOVE = f"""
    import time

    def f():
        {_DISABLE}wall-clock -- fixture: wall time IS the payload here
        return time.time()
"""


@pytest.mark.parametrize("src", [SUPPRESSED_INLINE, SUPPRESSED_ABOVE])
def test_suppression_with_reason_silences(tmp_path, src):
    r = lint_src(tmp_path, "serving/mod.py", src)
    assert not r.findings
    assert len(r.suppressed) == 1 and r.suppressed[0].rule == "wall-clock"


def test_suppression_without_reason_does_not_silence(tmp_path):
    r = lint_src(
        tmp_path,
        "serving/mod.py",
        f"""
        import time

        def f():
            return time.time()  {_DISABLE}wall-clock
        """,
    )
    rules = sorted(f.rule for f in r.findings)
    assert rules == ["suppression", "wall-clock"]  # original NOT suppressed
    assert "missing a reason" in next(
        f.message for f in r.findings if f.rule == "suppression"
    )


def test_suppression_unknown_rule_is_a_finding(tmp_path):
    r = lint_src(
        tmp_path,
        "mod.py",
        f"""
        x = 1  {_DISABLE}wall-clocks -- typo'd rule id
        """,
    )
    assert [f.rule for f in r.findings] == ["suppression"]
    assert "unknown rule" in r.findings[0].message


# ------------------------------------------------------------- baseline
def test_baseline_round_trip_and_line_drift(tmp_path):
    mod = tmp_path / "serving" / "mod.py"
    mod.parent.mkdir(parents=True)
    mod.write_text("import time\n\ndef f():\n    return time.time()\n")
    first = run_lint([tmp_path], root=tmp_path)
    assert len(first.findings) == 1

    bl = tmp_path / "baseline.json"
    write_baseline(bl, first.findings)
    new, old = split_baselined(first.findings, load_baseline(bl))
    assert not new and len(old) == 1

    # unrelated edit shifts the line: the finding stays baselined
    mod.write_text(
        "import time\n\nPAD = 1\n\n\ndef f():\n    return time.time()\n"
    )
    drifted = run_lint([tmp_path], root=tmp_path)
    assert drifted.findings[0].line != first.findings[0].line
    new, old = split_baselined(drifted.findings, load_baseline(bl))
    assert not new and len(old) == 1

    # a NEW kind of finding is not masked by the old baseline
    mod.write_text(
        "import time\nfrom datetime import datetime\n\n"
        "def f():\n    return time.time(), datetime.now()\n"
    )
    new, old = split_baselined(
        run_lint([tmp_path], root=tmp_path).findings, load_baseline(bl)
    )
    assert len(new) == 1 and new[0].symbol == "datetime.datetime.now"


def test_baseline_missing_is_empty_and_corrupt_raises(tmp_path):
    assert load_baseline(tmp_path / "nope.json") == []
    bad = tmp_path / "bad.json"
    bad.write_text('{"version": 99, "findings": []}')
    with pytest.raises(ValueError, match="version"):
        load_baseline(bad)


# ------------------------------------------------------- JSON reporter
def test_json_reporter_schema(tmp_path):
    r = lint_src(tmp_path, "serving/mod.py", WALL_BAD)
    payload = json.loads(render_json(r, r.findings, []))
    assert payload["version"] == 1
    assert payload["files"] == 1
    assert payload["counts"]["new"] == 2
    assert payload["counts"]["baselined"] == 0
    assert payload["counts"]["by_rule"] == {"wall-clock": 2}
    for f in payload["findings"]:
        assert set(f) == {"rule", "path", "line", "message", "symbol"}
        assert f["path"] == "serving/mod.py"
    # deterministic ordering: (path, line, rule, message)
    assert payload["findings"] == sorted(
        payload["findings"], key=lambda f: (f["path"], f["line"], f["rule"])
    )
    human = render_human(r, r.findings, [])
    assert "2 findings" in human and "serving/mod.py:" in human


# ------------------------------------------------------------ CLI smoke
def _cli(args, cwd):
    env_path = str(REPO / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", *args],
        capture_output=True,
        text=True,
        cwd=cwd,
        env={"PYTHONPATH": env_path, "PATH": "/usr/bin:/bin:/usr/local/bin"},
    )


def test_cli_exit_codes_and_json_output(tmp_path):
    (tmp_path / "serving").mkdir()
    (tmp_path / "serving" / "mod.py").write_text(
        "import time\nT = time.time()\n"
    )
    dirty = _cli(
        ["serving", "--format", "json", "--output", "report.json"], tmp_path
    )
    assert dirty.returncode == 1, dirty.stderr
    report = json.loads((tmp_path / "report.json").read_text())
    assert report["counts"]["new"] == 1

    wrote = _cli(["serving", "--write-baseline"], tmp_path)
    assert wrote.returncode == 0, wrote.stderr
    clean = _cli(["serving"], tmp_path)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "(1 baselined" in clean.stdout


# ------------------------------------------------------------ self-lint
def test_self_lint_repo_is_clean():
    """Tier-1 gate: the whole tree lints clean with an EMPTY baseline —
    every invariant the rules encode holds everywhere, and any new
    violation fails this test before CI even reaches the lint step."""
    result = run_lint(
        [REPO / "src", REPO / "tests", REPO / "benchmarks", REPO / "examples"],
        root=REPO,
    )
    assert result.files > 100
    offenders = "\n".join(f.render() for f in result.findings)
    assert not result.findings, f"replint findings:\n{offenders}"
    # the committed baseline stays empty (acceptance criterion)
    assert load_baseline(REPO / ".replint-baseline.json") == []
