"""graftlint gate: every check fires on its seeded-violation fixture,
stays quiet on the clean counterpart, the baseline/suppression
machinery round-trips, the CLI honors its exit-code contract, and the
shipped tree has zero non-baselined findings.

Pure AST work — nothing here imports jax or touches a device, so the
whole module runs in milliseconds.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from generativeaiexamples_tpu.lint import Baseline, lint_paths
from generativeaiexamples_tpu.lint.cli import UsageError, resolve_checks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "generativeaiexamples_tpu")
CLI = [sys.executable, "-m", "generativeaiexamples_tpu.lint"]
# The package is not pip-installed: a CLI run from another directory
# needs the repository on its path.
CLI_ENV = dict(os.environ, PYTHONPATH=os.pathsep.join(
    p for p in (REPO, os.environ.get("PYTHONPATH", "")) if p))


def write_tree(root, files):
    for rel, src in files.items():
        path = os.path.join(str(root), rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fh:
            fh.write(textwrap.dedent(src))
    return str(root)


def ids_of(findings):
    return {f.check for f in findings}


# ---------------------------------------------------------------------------
# fixtures: one seeded-violation + one minimal clean file per check
# ---------------------------------------------------------------------------

TRACE_BAD = """\
    import functools

    import jax
    import numpy as np


    @functools.partial(jax.jit, static_argnames=("flag",))
    def step(x, flag):
        if flag:            # static arg: fine
            x = x + 1
        if x > 0:           # traced condition
            x = x * 2
        v = x.item()        # host sync
        f = float(x)        # concretization
        a = np.asarray(x)   # host materialization
        return x, v, f, a


    peek = jax.jit(lambda p: p.item())  # jit-wrapped lambda host sync
"""

TRACE_CLEAN = """\
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np


    @functools.partial(jax.jit, static_argnames=("flag",))
    def step(x, flag, y=None):
        if flag:                 # static arg
            x = x + 1
        if y is None:            # identity test: concrete at trace
            y = jnp.zeros_like(x)
        if x.ndim > 1:           # shape metadata: concrete at trace
            x = x.reshape(-1)
        x = jnp.where(x > 0, x * 2, x)
        return x + y + float(1.5)   # literal coercion: fine


    def host_side(x):
        return float(np.asarray(x).sum())  # not jitted: fine
"""

LOCK_BAD = """\
    import threading


    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def bump(self):
            with self._lock:
                self._n += 1

        def reset(self):
            self._n = 0  # bare write to a lock-guarded attribute
"""

LOCK_CLEAN = """\
    import threading


    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def bump(self):
            with self._lock:
                self._n += 1

        def reset(self):
            with self._lock:
                self._clear()

        def _clear(self):
            \"\"\"Lock held (callers own self._lock).\"\"\"
            self._n = 0
"""

THREAD_BAD = """\
    import threading


    class Worker:
        def start(self):
            self._t = threading.Thread(target=self._loop)
            self._t.start()

        def _loop(self):
            while True:
                try:
                    self._work()
                except Exception:
                    pass

        def _work(self):
            raise ValueError("boom")
"""

THREAD_CLEAN = """\
    import logging
    import threading

    _LOG = logging.getLogger(__name__)


    class Worker:
        def start(self):
            self._t = threading.Thread(target=self._loop, daemon=True)
            self._t.start()

        def _loop(self):
            while True:
                try:
                    self._work()
                except ValueError:
                    return  # narrow catch: not the broad-swallow shape
                except Exception:
                    _LOG.exception("worker failed")

        def _work(self):
            raise ValueError("boom")
"""

HOT_BAD = """\
    import jax
    import numpy as np


    class Engine:
        def _step(self):  # graftlint: hot-path
            jax.block_until_ready(self._tokens)
            got = jax.device_get(self._tokens)
            out = np.asarray(self._tokens)
            return got, out
"""

HOT_CLEAN = """\
    import jax
    import numpy as np


    class Engine:
        def warmup(self):  # not a hot path: syncs are fine here
            jax.block_until_ready(self._tokens)
            return np.asarray(self._tokens)

        def _step(self):  # graftlint: hot-path
            return self._dispatch()  # async dispatch only
"""

# GL402: the sync lives in a helper the root reaches only through the
# call graph (self-dispatch + a module-level function) — per-function
# scanning (the pre-inference GL401) cannot see it.
INFER_BAD = """\
    import jax


    def fetch_stats(arr):
        return jax.device_get(arr)


    class Engine:
        def _loop(self):
            while True:
                self._dispatch()

        def _dispatch(self):
            jax.block_until_ready(self._tokens)  # helper, not a root
            return fetch_stats(self._tokens)
"""

INFER_CLEAN = """\
    import jax


    def fetch_stats(arr):
        return jax.device_get(arr)  # never called from a hot root


    class Engine:
        def _loop(self):
            while True:
                self._dispatch()

        def _dispatch(self):
            return self._issue()  # async; syncs stay off this path

        def _issue(self):
            return 1

        def debug_dump(self):
            return fetch_stats(self._tokens)  # cold path: fine
"""

# GL202: the worker thread writes _n under the lock, the public surface
# reads it bare — no common lock on any call path. The clean twin locks
# the public read; _peek shows call-site-verified lock inheritance (it
# is ONLY called under the lock, so its read counts as locked).
RACE_BAD = """\
    import threading


    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def start(self):
            threading.Thread(target=self._work, daemon=True).start()

        def _work(self):
            with self._lock:
                self._n += 1

        def progress(self):
            return self._n  # bare read racing the worker's writes
"""

RACE_CLEAN = """\
    import threading


    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._n = 0

        def start(self):
            threading.Thread(target=self._work, daemon=True).start()

        def _work(self):
            with self._lock:
                self._n += 1

        def progress(self):
            with self._lock:
                return self._peek()

        def _peek(self):
            return self._n  # called only under the lock: locked
"""

# GL202's docstring verification: 'Lock held' is a checked claim now.
DOCSTRING_BAD = """\
    import threading


    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._v = 0

        def set(self, v):
            self._store(v)  # lock-free call into a 'Lock held' method

        def locked_set(self, v):
            with self._lock:
                self._store(v)

        def _store(self, v):
            \"\"\"Lock held.\"\"\"
            self._v = v
"""

# GL601: `dropped` is incremented but snapshot() never surfaces it;
# `lost` is incremented on a resolved instance attribute from another
# class. The clean twin surfaces both (one via a rename-read, one as a
# literal key).
METRICS_BAD = """\
    class Stats:
        def __init__(self):
            self.served = 0
            self.dropped = 0
            self.lost = 0

        def note(self):
            self.served += 1
            self.dropped += 1

        def snapshot(self):
            return {"served": self.served}


    class Owner:
        def __init__(self):
            self.stats = Stats()

        def fail(self):
            self.stats.lost += 1
"""

METRICS_CLEAN = """\
    class Stats:
        def __init__(self):
            self.served = 0
            self.dropped = 0
            self.lost = 0

        def note(self):
            self.served += 1
            self.dropped += 1

        def snapshot(self):
            return {"served": self.served,
                    "requests_dropped": self.dropped,  # rename-read
                    "lost": self.lost}


    class Owner:
        def __init__(self):
            self.stats = Stats()

        def fail(self):
            self.stats.lost += 1
"""

# GL601 over a histogram-shaped class (the serving/flight.py
# ExpHistogram idiom): observe() increments count/total per sample
# alongside the bucket array; the BAD twin's snapshot() surfaces the
# buckets but silently drops `overflowed` — a counter that can never
# reach /metrics. The clean twin reads every incremented attr.
HIST_METRICS_BAD = """\
    class Hist:
        def __init__(self):
            self.counts = [0] * 8
            self.count = 0
            self.total = 0.0
            self.overflowed = 0

        def observe(self, v):
            if v > 100:
                self.overflowed += 1
            self.count += 1
            self.total += v

        def snapshot(self):
            return {"count": self.count, "sum": self.total,
                    "buckets": list(self.counts)}
"""

HIST_METRICS_CLEAN = """\
    class Hist:
        def __init__(self):
            self.counts = [0] * 8
            self.count = 0
            self.total = 0.0
            self.overflowed = 0

        def observe(self, v):
            if v > 100:
                self.overflowed += 1
            self.count += 1
            self.total += v

        def snapshot(self):
            return {"count": self.count, "sum": self.total,
                    "overflow": self.overflowed,
                    "buckets": list(self.counts)}
"""

# GL502: save() rewrites the artifact in place; the clean twin stages
# through a tmp name and os.replace()s it into place. `_write_rows` is
# only a sink because its CALLER provably works under persist_dir.
PERSIST_BAD = """\
    import json
    import os


    def _write_rows(rows, path):
        with open(path, "w") as fh:
            json.dump(rows, fh)


    class Store:
        def save(self, path):
            with open(path, "w") as fh:
                json.dump(self._rows, fh)

        def persist(self):
            _write_rows(self._rows,
                        os.path.join(self.persist_dir, "rows.json"))
"""

PERSIST_CLEAN = """\
    import json
    import os


    class Store:
        def save(self, path):
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(self._rows, fh)
            os.replace(tmp, path)

        def export_debug(self, path):
            with open(path, "w") as fh:  # not a persisted artifact
                json.dump(self._rows, fh)
"""

CONFIG_SCHEMA = """\
    from dataclasses import dataclass, field


    @dataclass(frozen=True)
    class FooConfig:
        alpha: int = 1
        beta: str = ""


    @dataclass(frozen=True)
    class AppConfig:
        foo: FooConfig = field(default_factory=FooConfig)
"""

CONFIG_DOCS_FULL = """\
    # Configuration reference

    ## `foo`

    | field | default | env var |
    |---|---|---|
    | `alpha` | `1` | `APP_FOO_ALPHA` |
    | `beta` | `""` | `APP_FOO_BETA` |
"""

CONFIG_DOCS_MISSING_BETA = """\
    # Configuration reference

    ## `foo`

    | field | default | env var |
    |---|---|---|
    | `alpha` | `1` | `APP_FOO_ALPHA` |
"""

CONFIG_APP_BAD = """\
    import os


    def use(cfg):
        a = getattr(cfg, "alpha", None)        # resolves: fine
        g = getattr(cfg, "gamma", None)        # no such knob
        v = os.environ.get("APP_FOO_NOPE")     # no such env name
        return a, g, v
"""

CONFIG_APP_CLEAN = """\
    import os


    def use(cfg):
        a = getattr(cfg, "alpha", None)
        section = getattr(cfg, "foo", None)
        v = os.environ.get("APP_FOO_BETA")
        w = os.environ.get("APP_CONFIG_FILE")  # whitelisted loader knob
        return a, section, v, w
"""


# GL70x multihost collective-safety: every fixture is a file named
# engine.py so `_loop` registers as the scheduler root.

MH_PUBLISH_BAD = """\
    import functools

    import jax


    @functools.partial(jax.jit, static_argnames=("n",))
    def plan_step(state, n):
        return state


    class DispatchLog:
        def publish(self, record):
            return record


    class Engine:
        def __init__(self):
            self._mh_log = DispatchLog()

        def _loop(self):
            self._dispatch_plan(1)

        def _dispatch_plan(self, n):
            out = plan_step({}, n)               # launched first ...
            self._mh_log.publish(("plan", n))    # ... published after
            return out
"""

MH_PUBLISH_CLEAN = """\
    import functools

    import jax


    @functools.partial(jax.jit, static_argnames=("n",))
    def plan_step(state, n):
        return state


    class DispatchLog:
        def publish(self, record):
            return record


    class Engine:
        def __init__(self):
            self._mh_log = DispatchLog()

        def _loop(self):
            self._dispatch_plan(1)

        def _dispatch_plan(self, n):
            self._mh_log.publish(("plan", n))    # publish, THEN launch
            return plan_step({}, n)
"""

MH_FETCH_BAD = """\
    import numpy as np


    class Engine:
        def _loop(self):
            self._emit()

        def _emit(self):
            return np.asarray(self._last_dev)  # bypasses the fetch seams
"""

MH_FETCH_CLEAN = """\
    import numpy as np


    def fetch_replicated(arr):
        return np.asarray(arr)


    class Engine:
        def _loop(self):
            self._emit()

        def _emit(self):
            return fetch_replicated(self._last)
"""

MH_DIVERGE_BAD = """\
    import functools
    import time

    import jax


    @functools.partial(jax.jit, static_argnames=("n",))
    def plan_step(state, n):
        return state


    class DispatchLog:
        def publish(self, record):
            return record


    class Engine:
        def __init__(self):
            self._mh_log = DispatchLog()
            self._tiers = {"bulk", "interactive"}

        def _loop(self):
            n = self._pick_width()
            self._mh_log.publish(("plan", n))
            plan_step({}, n)

        def _pick_width(self):
            for tier in self._tiers:               # unordered iteration
                if tier == "interactive":
                    return 1
            return int(time.perf_counter()) % 4    # wall-clock decision
"""

MH_DIVERGE_CLEAN = """\
    import functools

    import jax


    @functools.partial(jax.jit, static_argnames=("n",))
    def plan_step(state, n):
        return state


    class DispatchLog:
        def publish(self, record):
            return record


    class Engine:
        def __init__(self):
            self._mh_log = DispatchLog()
            self._widths = [1, 2, 4]

        def _loop(self):
            n = self._pick_width()
            self._mh_log.publish(("plan", n))
            plan_step({}, n)

        def _pick_width(self):
            return self._widths[0]   # deterministic scheduler state
"""

MH_RANK_BAD = """\
    import functools

    import jax


    @functools.partial(jax.jit, static_argnames=("n",))
    def plan_step(state, n):
        return state


    class DispatchLog:
        def publish(self, record):
            return record


    class Engine:
        def __init__(self):
            self._mh_log = DispatchLog()
            self._mh_leader = True

        def _loop(self):
            self._mh_log.publish("plan")
            if self._mh_leader:
                plan_step({}, 1)   # guarded launch: ranks diverge
"""

MH_RANK_CLEAN = """\
    import functools

    import jax


    @functools.partial(jax.jit, static_argnames=("n",))
    def plan_step(state, n):
        return state


    class DispatchLog:
        def publish(self, record):
            return record


    class Engine:
        def __init__(self):
            self._mh_log = DispatchLog()
            self._mh_leader = True

        def _loop(self):
            if self._mh_leader:                  # leader-guarded PUBLISH
                self._mh_log.publish("plan")     # is the protocol: quiet
            plan_step({}, 1)                     # launch on every rank
"""


# ---------------------------------------------------------------------------
# per-check detection
# ---------------------------------------------------------------------------


class TestTracePurity:
    def test_fires_on_seeded_violations(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path, {"mod.py": TRACE_BAD})])
        gl101 = [f for f in findings if f.check == "GL101"]
        # traced if + .item() + float() + np.asarray + lambda .item()
        assert len(gl101) == 5
        msgs = " ".join(f.message for f in gl101)
        assert ".item()" in msgs
        assert "float()" in msgs
        assert "np.asarray" in msgs
        assert "`if`" in msgs

    def test_quiet_on_clean(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path, {"mod.py": TRACE_CLEAN})])
        assert ids_of(findings) == set()


class TestLockDiscipline:
    def test_fires_on_bare_write(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path, {"mod.py": LOCK_BAD})])
        gl201 = [f for f in findings if f.check == "GL201"]
        assert len(gl201) == 1
        assert "_n" in gl201[0].message
        assert "reset" in gl201[0].message

    def test_quiet_on_clean_and_lock_held_doc(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path, {"mod.py": LOCK_CLEAN})])
        assert ids_of(findings) == set()

    def test_init_writes_exempt(self, tmp_path):
        # __init__ seeds attributes bare by design — never a finding.
        findings = lint_paths([write_tree(tmp_path, {"mod.py": LOCK_BAD})])
        assert all(f.line != 7 for f in findings)


class TestThreadHygiene:
    def test_fires_on_non_daemon_and_swallow(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path, {"mod.py": THREAD_BAD})])
        assert "GL301" in ids_of(findings)
        assert "GL302" in ids_of(findings)
        gl302 = [f for f in findings if f.check == "GL302"]
        assert "Worker._loop" in gl302[0].message

    def test_quiet_on_clean(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path,
                                          {"mod.py": THREAD_CLEAN})])
        assert ids_of(findings) == set()


class TestHostSync:
    def test_fires_in_marked_hot_path(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path, {"mod.py": HOT_BAD})])
        gl401 = [f for f in findings if f.check == "GL401"]
        assert len(gl401) == 3  # block_until_ready + device_get + asarray

    def test_engine_root_applies_without_marker(self, tmp_path):
        # In a file named engine.py the scheduler root `_loop` is hot
        # with no marker (HOT_ROOTS).
        src = HOT_BAD.replace("def _step(self):  # graftlint: hot-path",
                              "def _loop(self):")
        findings = lint_paths([write_tree(tmp_path, {"engine.py": src})])
        assert "GL401" in ids_of(findings)

    def test_quiet_outside_hot_path(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path, {"mod.py": HOT_CLEAN})])
        assert ids_of(findings) == set()

    def test_all_declared_roots_apply(self, tmp_path):
        # One root per serving dispatch loop (the whole HOT_ROOTS
        # surface): a sync in any of them fires with no marker.
        for i, (fname, fn) in enumerate((
                ("engine.py", "_loop"), ("batcher.py", "_run"),
                ("router.py", "place"), ("fleet.py", "submit"),
                ("qos.py", "pick"), ("tiered.py", "search"))):
            src = HOT_BAD.replace(
                "def _step(self):  # graftlint: hot-path",
                f"def {fn}(self):")
            root = write_tree(tmp_path / f"case{i}", {fname: src})
            assert "GL401" in ids_of(lint_paths([root])), (fname, fn)


class TestHotPathInference:
    def test_fires_through_the_call_graph(self, tmp_path):
        # The syncs sit in a self-dispatched helper and a module-level
        # function — reachable from engine._loop only via call edges.
        findings = lint_paths([write_tree(tmp_path,
                                          {"engine.py": INFER_BAD})])
        gl402 = [f for f in findings if f.check == "GL402"]
        assert len(gl402) == 2
        msgs = " ".join(f.message for f in gl402)
        assert "hot via" in msgs            # self-justifying chain
        assert "engine.py:Engine._loop" in msgs

    def test_quiet_off_the_hot_graph(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path,
                                          {"engine.py": INFER_CLEAN})])
        assert ids_of(findings) == set()

    def test_inferred_set_is_superset_of_pre_pr_hot_defaults(self):
        # Pin: the call-graph-inferred hot set must cover every entry
        # of the hand-maintained HOT_DEFAULTS dict this PR deleted
        # (lint/checks/host_sync.py:38 as of PR 9) — for EVERY module.
        # A regression here means a dispatch-path helper silently left
        # the scanned set.
        from generativeaiexamples_tpu.lint import callgraph
        from generativeaiexamples_tpu.lint.checks import host_sync
        from generativeaiexamples_tpu.lint.core import load_project

        pre_pr_hot_defaults = {
            # _dispatch_plan became _exec_plan when the dispatch
            # helpers were recast as multihost record executors; the
            # pin follows the rename (same dispatch site).
            "engine.py": {"_loop", "_admit_waiting", "_dispatch_decode",
                          "_select_plan", "_exec_plan",
                          "_rider_candidate", "_advance_long_prefills",
                          "_emit_ready_first_tokens", "_qos_pop_waiting",
                          "_qos_refresh_preemption",
                          "_qos_latency_pressure"},
            "batcher.py": {"_loop", "_run", "_take_group"},
            "qos.py": {"pick", "note_admitted", "try_admit"},
            "router.py": {"place", "_choose", "_score", "_apply_reports"},
            "fleet.py": {"submit", "_on_event"},
            "tiered.py": {"search", "_host_refine", "_merge"},
        }
        project = load_project([PKG])
        graph = callgraph.build(project)
        hot = host_sync.inferred_hot(graph)
        by_mod = {}
        for key in hot:
            node = graph.nodes[key]
            by_mod.setdefault(node.module, set()).add(node.name)
        for mod, fns in pre_pr_hot_defaults.items():
            missing = fns - by_mod.get(mod, set())
            assert not missing, (mod, missing)
        # STRICT superset: inference reaches helpers the dict never
        # listed (e.g. the prefill group path under _admit_waiting).
        assert "_prefill_group" in by_mod["engine.py"]
        total_old = sum(len(v) for v in pre_pr_hot_defaults.values())
        assert len(hot) > total_old


class TestCrossThreadRace:
    def test_fires_on_unlocked_public_read(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path, {"mod.py": RACE_BAD})])
        gl202 = [f for f in findings if f.check == "GL202"]
        assert len(gl202) == 1
        assert "_n" in gl202[0].message
        assert "progress" in gl202[0].message

    def test_quiet_when_callsite_verified_locked(self, tmp_path):
        # progress() locks; _peek is invoked ONLY from under the lock,
        # so its read counts as locked without any docstring.
        findings = lint_paths([write_tree(tmp_path,
                                          {"mod.py": RACE_CLEAN})])
        assert ids_of(findings) == set()

    def test_lock_held_docstring_is_verified(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path,
                                          {"mod.py": DOCSTRING_BAD})])
        gl202 = [f for f in findings if f.check == "GL202"]
        assert len(gl202) == 1
        assert "Lock held" in gl202[0].message
        assert "set" in gl202[0].message  # the violating caller, named

    def test_docstring_clean_when_all_callsites_locked(self, tmp_path):
        src = DOCSTRING_BAD.replace(
            "        def set(self, v):\n"
            "            self._store(v)  # lock-free call into a "
            "'Lock held' method\n",
            "        def set(self, v):\n"
            "            with self._lock:\n"
            "                self._store(v)\n")
        findings = lint_paths([write_tree(tmp_path, {"mod.py": src})])
        assert ids_of(findings) == set()


class TestMetricsContract:
    def test_fires_on_unsurfaced_counters(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path,
                                          {"mod.py": METRICS_BAD})])
        gl601 = [f for f in findings if f.check == "GL601"]
        assert len(gl601) == 2
        msgs = " ".join(f.message for f in gl601)
        assert "dropped" in msgs      # internal increment
        assert "lost" in msgs         # external, via attr dataflow
        assert "served" not in msgs   # surfaced: read by snapshot()

    def test_quiet_when_surfaced(self, tmp_path):
        # `dropped` is surfaced under a RENAMED key (the read is what
        # counts), `lost` as a literal key.
        findings = lint_paths([write_tree(tmp_path,
                                          {"mod.py": METRICS_CLEAN})])
        assert ids_of(findings) == set()

    def test_fires_on_unsurfaced_histogram_counter(self, tmp_path):
        # The flight-recorder histogram idiom: per-sample counters
        # incremented in observe() are under the same contract as any
        # scheduler counter — dropping one from snapshot() fires.
        findings = lint_paths([write_tree(tmp_path,
                                          {"mod.py": HIST_METRICS_BAD})])
        gl601 = [f for f in findings if f.check == "GL601"]
        assert len(gl601) == 1  # count/total surfaced -> quiet
        assert "overflowed" in gl601[0].message

    def test_quiet_on_fully_surfaced_histogram(self, tmp_path):
        findings = lint_paths([write_tree(
            tmp_path, {"mod.py": HIST_METRICS_CLEAN})])
        assert ids_of(findings) == set()

    def test_functional_state_exempt(self, tmp_path):
        # An incremented attr the class itself consumes (a cursor) is
        # state, not a lost counter.
        src = METRICS_BAD.replace(
            "        def snapshot(self):",
            "        def spin(self):\n"
            "            return self.dropped % 3\n\n"
            "        def snapshot(self):")
        findings = lint_paths([write_tree(tmp_path, {"mod.py": src})])
        assert all("dropped" not in f.message for f in findings
                   if f.check == "GL601")


class TestAtomicPersistence:
    def test_fires_on_in_place_writes(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path,
                                          {"mod.py": PERSIST_BAD})])
        gl502 = [f for f in findings if f.check == "GL502"]
        # Store.save (name-scoped) + _write_rows (reverse-call-chain
        # taint through the persist_dir-handling caller).
        assert len(gl502) == 2
        msgs = " ".join(f.message for f in gl502)
        assert "Store.save" in msgs
        assert "persist_dir" in msgs

    def test_quiet_on_tmp_replace_idiom(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path,
                                          {"mod.py": PERSIST_CLEAN})])
        assert ids_of(findings) == set()


class TestConfigDrift:
    def test_fires_on_all_three_drift_shapes(self, tmp_path):
        root = write_tree(tmp_path, {
            "pkg/config/schema.py": CONFIG_SCHEMA,
            "pkg/app.py": CONFIG_APP_BAD,
            "docs/configuration.md": CONFIG_DOCS_MISSING_BETA,
        })
        findings = lint_paths([root])
        # GL505/GL506 (renamed from GL502/GL503 when GL502 became the
        # atomic-persistence check): same three drift shapes.
        assert {"GL501", "GL505", "GL506"} <= ids_of(findings)
        by = {f.check: f for f in findings}
        assert "foo.beta" in by["GL501"].message
        assert "gamma" in by["GL505"].message
        assert "APP_FOO_NOPE" in by["GL506"].message

    def test_quiet_when_in_sync(self, tmp_path):
        root = write_tree(tmp_path, {
            "pkg/config/schema.py": CONFIG_SCHEMA,
            "pkg/app.py": CONFIG_APP_CLEAN,
            "docs/configuration.md": CONFIG_DOCS_FULL,
        })
        assert ids_of(lint_paths([root])) == set()

    def test_inactive_without_schema(self, tmp_path):
        # Linting a subtree that doesn't include config/schema.py must
        # not fail on unresolvable knob references.
        root = write_tree(tmp_path, {"pkg/app.py": CONFIG_APP_BAD})
        assert ids_of(lint_paths([root])) == set()


# ---------------------------------------------------------------------------
# findings / baseline machinery
# ---------------------------------------------------------------------------


class TestMultihostPublish:
    def test_fires_on_publish_after_launch(self, tmp_path):
        findings = lint_paths(
            [write_tree(tmp_path, {"engine.py": MH_PUBLISH_BAD})])
        gl701 = [f for f in findings if f.check == "GL701"]
        assert len(gl701) == 1, [f.format() for f in findings]
        msg = gl701[0].message
        assert "plan_step" in msg
        assert "DispatchLog.publish" in msg
        # the finding embeds its scheduler-root->dispatch chain
        assert "Engine._loop" in msg and "Engine._dispatch_plan" in msg
        assert "--explain-dispatch-site" in msg

    def test_quiet_when_published_before_launch(self, tmp_path):
        findings = lint_paths(
            [write_tree(tmp_path, {"engine.py": MH_PUBLISH_CLEAN})])
        assert ids_of(findings) == set()


class TestMultihostFetchSeam:
    def test_fires_on_raw_materialization(self, tmp_path):
        findings = lint_paths(
            [write_tree(tmp_path, {"engine.py": MH_FETCH_BAD})])
        gl702 = [f for f in findings if f.check == "GL702"]
        assert len(gl702) == 1, [f.format() for f in findings]
        assert "fetch_replicated" in gl702[0].message

    def test_quiet_through_the_sanctioned_seam(self, tmp_path):
        findings = lint_paths(
            [write_tree(tmp_path, {"engine.py": MH_FETCH_CLEAN})])
        assert ids_of(findings) == set()


class TestMultihostDivergence:
    def test_fires_on_clock_and_set_iteration(self, tmp_path):
        findings = lint_paths(
            [write_tree(tmp_path, {"engine.py": MH_DIVERGE_BAD})])
        gl703 = [f for f in findings if f.check == "GL703"]
        msgs = " ".join(f.message for f in gl703)
        assert len(gl703) == 2, [f.format() for f in findings]
        assert "wall-clock" in msgs
        assert "unordered set" in msgs

    def test_quiet_on_deterministic_decision(self, tmp_path):
        findings = lint_paths(
            [write_tree(tmp_path, {"engine.py": MH_DIVERGE_CLEAN})])
        assert ids_of(findings) == set()


class TestMultihostRankBranch:
    def test_fires_on_guarded_launch(self, tmp_path):
        findings = lint_paths(
            [write_tree(tmp_path, {"engine.py": MH_RANK_BAD})])
        gl704 = [f for f in findings if f.check == "GL704"]
        assert len(gl704) == 1, [f.format() for f in findings]
        assert "plan_step" in gl704[0].message

    def test_leader_guarded_publish_is_quiet(self, tmp_path):
        findings = lint_paths(
            [write_tree(tmp_path, {"engine.py": MH_RANK_CLEAN})])
        assert ids_of(findings) == set()


class TestDispatchInventoryPin:
    """The replay protocol's known-good set: scripts/smoke_multihost.py
    drives prefill, token feedback, and decode through the DispatchLog.
    The GL701 inventory must see AT LEAST those dispatch points — if a
    refactor renames a lane out of the inventory, a new unpublished
    dispatch could land silently and this pin fails first."""

    SMOKE_DISPATCHES = {"prefill_batch_step", "set_last_tokens",
                        "plan_step"}

    def test_inventory_superset_of_smoke_dispatches(self):
        from generativeaiexamples_tpu.lint import callgraph
        from generativeaiexamples_tpu.lint.checks.multihost_safety \
            import inventory_for
        from generativeaiexamples_tpu.lint.core import load_project

        inv = inventory_for(load_project([PKG]))
        reachable = {callgraph.entry_name(dst)
                     for _, _, dst in inv.reachable_sites()}
        missing = self.SMOKE_DISPATCHES - reachable
        assert not missing, (
            f"dispatch points exercised by scripts/smoke_multihost.py "
            f"missing from the scheduler-reachable GL701 inventory: "
            f"{sorted(missing)}; reachable={sorted(reachable)}")


class TestSuppression:
    def test_inline_ignore_on_finding_line(self, tmp_path):
        src = LOCK_BAD.replace(
            "self._n = 0  # bare write to a lock-guarded attribute",
            "self._n = 0  # graftlint: ignore[GL201]")
        assert ids_of(lint_paths([write_tree(tmp_path,
                                             {"mod.py": src})])) == set()

    def test_inline_ignore_on_def_line_covers_function(self, tmp_path):
        src = LOCK_BAD.replace("def reset(self):",
                               "def reset(self):  # graftlint: ignore[GL201]")
        assert ids_of(lint_paths([write_tree(tmp_path,
                                             {"mod.py": src})])) == set()

    def test_inline_ignore_wrong_id_keeps_finding(self, tmp_path):
        src = LOCK_BAD.replace(
            "self._n = 0  # bare write to a lock-guarded attribute",
            "self._n = 0  # graftlint: ignore[GL999]")
        assert "GL201" in ids_of(
            lint_paths([write_tree(tmp_path, {"mod.py": src})]))


class TestBaseline:
    def test_roundtrip_suppresses(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path / "a",
                                          {"mod.py": LOCK_BAD})])
        assert findings
        bl = Baseline.from_findings(findings)
        assert bl.filter(findings) == []
        assert bl.unused_entries() == []

    def test_save_load_roundtrip(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path / "a",
                                          {"mod.py": LOCK_BAD})])
        path = str(tmp_path / "baseline.json")
        Baseline.from_findings(findings).save(path)
        bl = Baseline.load(path)
        assert bl.filter(findings) == []
        data = json.load(open(path))
        assert data["version"] == 1
        assert all({"check", "file", "line", "hash", "reason"}
                   <= set(e) for e in data["entries"])

    def test_line_drift_tolerated(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path / "a",
                                          {"mod.py": LOCK_BAD})])
        bl = Baseline.from_findings(findings)
        # Same code, pushed 7 lines down: hash matching still holds.
        drifted = "# pad\n" * 7 + textwrap.dedent(LOCK_BAD)
        f2 = lint_paths([write_tree(tmp_path / "b", {"mod.py": drifted})])
        assert f2 and f2[0].line != findings[0].line
        assert bl.filter(f2) == []

    def test_file_move_tolerated(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path / "a",
                                          {"mod.py": LOCK_BAD})])
        bl = Baseline.from_findings(findings)
        f2 = lint_paths([write_tree(tmp_path / "b",
                                    {"moved/renamed.py": LOCK_BAD})])
        assert f2 and f2[0].path != findings[0].path
        assert bl.filter(f2) == []

    def test_edited_line_invalidates(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path / "a",
                                          {"mod.py": LOCK_BAD})])
        bl = Baseline.from_findings(findings)
        edited = LOCK_BAD.replace("self._n = 0  #", "self._n = 1  #")
        f2 = lint_paths([write_tree(tmp_path / "b", {"mod.py": edited})])
        assert f2 and bl.filter(f2) == f2  # suppression no longer applies

    def test_regenerate_preserves_curated_reasons(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path / "a",
                                          {"mod.py": LOCK_BAD})])
        bl = Baseline.from_findings(findings)
        bl.entries[0]["reason"] = "carefully justified"
        regen = Baseline.from_findings(findings, previous=Baseline(
            bl.entries))
        assert regen.entries[0]["reason"] == "carefully justified"

    def test_stale_entries_reported(self, tmp_path):
        findings = lint_paths([write_tree(tmp_path / "a",
                                          {"mod.py": LOCK_BAD})])
        bl = Baseline.from_findings(findings)
        clean = lint_paths([write_tree(tmp_path / "b",
                                       {"mod.py": LOCK_CLEAN})])
        assert bl.filter(clean) == []
        assert len(bl.unused_entries()) == len(bl)


class TestSeverityAndSelection:
    def test_min_severity_filters_warnings(self, tmp_path):
        root = write_tree(tmp_path, {"mod.py": LOCK_BAD})
        assert "GL201" in ids_of(lint_paths([root]))
        assert ids_of(lint_paths([root], min_severity="error")) == set()

    def test_select_and_ignore(self, tmp_path):
        root = write_tree(tmp_path, {"lk.py": LOCK_BAD,
                                     "tr.py": TRACE_BAD})
        only = lint_paths([root], select=["GL101"])
        assert ids_of(only) == {"GL101"}
        rest = lint_paths([root], ignore=["GL101"])
        assert "GL101" not in ids_of(rest)
        assert "GL201" in ids_of(rest)

    def test_unknown_check_id_rejected(self):
        with pytest.raises(UsageError):
            resolve_checks(["GL999"], None)

    def test_syntax_error_surfaces_as_finding(self, tmp_path):
        root = write_tree(tmp_path, {"broken.py": "def f(:\n"})
        findings = lint_paths([root])
        assert ids_of(findings) == {"GL000"}


# ---------------------------------------------------------------------------
# CLI exit-code contract: 0 clean, 1 findings, 2 usage error
# ---------------------------------------------------------------------------


def run_cli(*args):
    return subprocess.run(CLI + list(args), cwd=REPO, text=True,
                          capture_output=True, timeout=120)


class TestCLI:
    def test_exit_0_on_clean_tree(self, tmp_path):
        root = write_tree(tmp_path, {"mod.py": TRACE_CLEAN})
        proc = run_cli(root, "--no-baseline")
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_exit_1_on_findings(self, tmp_path):
        root = write_tree(tmp_path, {"mod.py": TRACE_BAD})
        proc = run_cli(root, "--no-baseline")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "GL101" in proc.stdout

    @pytest.mark.parametrize("check_id,files", [
        ("GL101", {"mod.py": TRACE_BAD}),
        ("GL201", {"mod.py": LOCK_BAD}),
        ("GL202", {"mod.py": RACE_BAD}),
        ("GL301", {"mod.py": THREAD_BAD}),
        ("GL302", {"mod.py": THREAD_BAD}),
        ("GL401", {"mod.py": HOT_BAD}),
        ("GL402", {"engine.py": INFER_BAD}),
        ("GL501", {"pkg/config/schema.py": CONFIG_SCHEMA,
                   "pkg/app.py": CONFIG_APP_BAD,
                   "docs/configuration.md": CONFIG_DOCS_MISSING_BETA}),
        ("GL502", {"mod.py": PERSIST_BAD}),
        ("GL601", {"mod.py": METRICS_BAD}),
        ("GL701", {"engine.py": MH_PUBLISH_BAD}),
        ("GL702", {"engine.py": MH_FETCH_BAD}),
        ("GL703", {"engine.py": MH_DIVERGE_BAD}),
        ("GL704", {"engine.py": MH_RANK_BAD}),
    ])
    def test_exit_1_per_seeded_fixture(self, tmp_path, check_id, files):
        root = write_tree(tmp_path, files)
        proc = run_cli(root, "--no-baseline")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert check_id in proc.stdout

    @pytest.mark.parametrize("files", [
        {"engine.py": INFER_CLEAN},
        {"mod.py": RACE_CLEAN},
        {"mod.py": METRICS_CLEAN},
        {"mod.py": PERSIST_CLEAN},
        {"engine.py": MH_PUBLISH_CLEAN},
        {"engine.py": MH_FETCH_CLEAN},
        {"engine.py": MH_DIVERGE_CLEAN},
        {"engine.py": MH_RANK_CLEAN},
    ])
    def test_exit_0_per_clean_counterpart(self, tmp_path, files):
        root = write_tree(tmp_path, files)
        proc = run_cli(root, "--no-baseline")
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_exit_2_on_bad_flag(self):
        assert run_cli("--definitely-not-a-flag").returncode == 2

    def test_exit_2_on_missing_path(self):
        proc = run_cli("/nonexistent/path/xyz")
        assert proc.returncode == 2
        assert "does not exist" in proc.stderr

    def test_exit_2_on_no_paths(self):
        assert run_cli().returncode == 2

    def test_exit_2_on_unknown_select(self, tmp_path):
        root = write_tree(tmp_path, {"mod.py": TRACE_CLEAN})
        proc = run_cli(root, "--select", "GL999")
        assert proc.returncode == 2
        assert "unknown check" in proc.stderr

    def test_list_checks(self):
        proc = run_cli("--list-checks")
        assert proc.returncode == 0
        for cid in ("GL101", "GL201", "GL202", "GL301", "GL302", "GL401",
                    "GL402", "GL501", "GL502", "GL601", "GL701", "GL702",
                    "GL703", "GL704"):
            assert cid in proc.stdout

    def test_json_format(self, tmp_path):
        root = write_tree(tmp_path, {"mod.py": LOCK_BAD})
        proc = run_cli(root, "--no-baseline", "--format", "json")
        assert proc.returncode == 1
        payload = json.loads(proc.stdout)
        assert payload[0]["check"] == "GL201"
        assert payload[0]["hash"]

    def test_write_baseline_then_clean(self, tmp_path):
        root = write_tree(tmp_path, {"mod.py": LOCK_BAD})
        bl_path = str(tmp_path / "bl.json")
        assert run_cli(root, "--write-baseline", bl_path).returncode == 0
        proc = run_cli(root, "--baseline", bl_path)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "1 baselined" in proc.stdout

    def test_explain_hot_path_prints_chain(self, tmp_path):
        root = write_tree(tmp_path, {"engine.py": INFER_BAD})
        proc = run_cli(root, "--explain-hot-path", "fetch_stats")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        # root -> helper -> function, in order, marked as a chain
        assert "is HOT" in proc.stdout
        assert proc.stdout.index("Engine._loop") \
            < proc.stdout.index("Engine._dispatch") \
            < proc.stdout.rindex("fetch_stats")
        assert "(root)" in proc.stdout

    def test_explain_dispatch_site_prints_root_first_chain(self, tmp_path):
        root = write_tree(tmp_path, {"engine.py": MH_PUBLISH_BAD})
        proc = run_cli(root, "--explain-dispatch-site", "_dispatch_plan")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "plan_step" in proc.stdout
        assert "UNPUBLISHED" in proc.stdout   # launched before publish
        # chain prints root-first: _loop (root) above _dispatch_plan
        loop_at = proc.stdout.index("Engine._loop (root)")
        site_at = proc.stdout.rindex("Engine._dispatch_plan")
        assert loop_at < site_at, proc.stdout

    def test_explain_dispatch_site_jit_entry_lists_holders(self, tmp_path):
        root = write_tree(tmp_path, {"engine.py": MH_PUBLISH_CLEAN})
        proc = run_cli(root, "--explain-dispatch-site", "plan_step")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "jit entry" in proc.stdout
        assert "Engine._dispatch_plan" in proc.stdout
        assert "published in-function" in proc.stdout

    def test_explain_dispatch_site_no_sites_exits_1(self, tmp_path):
        root = write_tree(tmp_path, {"engine.py": MH_PUBLISH_CLEAN})
        proc = run_cli(root, "--explain-dispatch-site", "publish")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "no dispatch sites" in proc.stdout

    def test_explain_dispatch_site_unknown_exits_2(self, tmp_path):
        root = write_tree(tmp_path, {"engine.py": MH_PUBLISH_CLEAN})
        proc = run_cli(root, "--explain-dispatch-site", "nope_never")
        assert proc.returncode == 2
        assert "no function matching" in proc.stderr

    def test_explain_hot_path_cold_function_exits_1(self, tmp_path):
        root = write_tree(tmp_path, {"engine.py": INFER_CLEAN})
        proc = run_cli(root, "--explain-hot-path", "debug_dump")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "not in the inferred hot set" in proc.stdout

    def test_explain_hot_path_unknown_exits_2(self, tmp_path):
        root = write_tree(tmp_path, {"engine.py": INFER_CLEAN})
        proc = run_cli(root, "--explain-hot-path", "no_such_function")
        assert proc.returncode == 2
        assert "no function matching" in proc.stderr

    def test_sarif_format(self, tmp_path):
        root = write_tree(tmp_path, {"mod.py": LOCK_BAD})
        proc = run_cli(root, "--no-baseline", "--format", "sarif")
        assert proc.returncode == 1
        doc = json.loads(proc.stdout)
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "graftlint"
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"GL101", "GL202", "GL402", "GL502", "GL601"} <= rule_ids
        res = run["results"][0]
        assert res["ruleId"] == "GL201"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("mod.py")
        assert loc["region"]["startLine"] > 0
        assert res["partialFingerprints"]["graftlintContentHash/v1"]

    def test_sarif_out_rides_the_gating_run(self, tmp_path):
        # --sarif-out writes the artifact in the SAME pass as the text
        # gate (ci_checks.sh relies on this: one lint run, two outputs).
        root = write_tree(tmp_path, {"mod.py": LOCK_BAD})
        out = str(tmp_path / "lint.sarif")
        proc = run_cli(root, "--no-baseline", "--sarif-out", out)
        assert proc.returncode == 1            # text gate still gates
        assert "GL201" in proc.stdout          # text output intact
        doc = json.load(open(out))
        assert doc["runs"][0]["results"][0]["ruleId"] == "GL201"

    def test_changed_rejects_write_baseline(self, tmp_path):
        # A diff-scoped regenerate would truncate the baseline to the
        # diff's findings, silently deleting curated entries.
        root = write_tree(tmp_path, {"mod.py": LOCK_BAD})
        proc = run_cli(root, "--changed", "--write-baseline",
                       str(tmp_path / "bl.json"))
        assert proc.returncode == 2
        assert "--write-baseline" in proc.stderr

    def test_fail_stale_exits_nonzero(self, tmp_path):
        # Baseline an entry, fix the code: --fail-stale turns the
        # formerly-informational stale report into a gate.
        root = write_tree(tmp_path, {"mod.py": LOCK_BAD})
        bl_path = str(tmp_path / "bl.json")
        assert run_cli(root, "--write-baseline", bl_path).returncode == 0
        fixed = write_tree(tmp_path / "fixed", {"mod.py": LOCK_CLEAN})
        ok = run_cli(fixed, "--baseline", bl_path)
        assert ok.returncode == 0  # stale is informational by default
        gated = run_cli(fixed, "--baseline", bl_path, "--fail-stale")
        assert gated.returncode == 1, gated.stdout + gated.stderr
        assert "stale baseline entry" in gated.stderr
        # the message names the owning check, not just the content
        # hash — a hash alone is undiagnosable in CI logs
        assert "GL201" in gated.stderr, gated.stderr

    def test_fail_stale_ignores_incomplete_runs(self, tmp_path):
        # A raised severity floor filters findings BEFORE the baseline
        # sees them; stale accounting must not mistake that for fixed
        # code (the entry's finding is warning-severity and still
        # present).
        root = write_tree(tmp_path, {"mod.py": LOCK_BAD})
        bl_path = str(tmp_path / "bl.json")
        assert run_cli(root, "--write-baseline", bl_path).returncode == 0
        proc = run_cli(root, "--baseline", bl_path, "--fail-stale",
                       "--min-severity", "error")
        assert proc.returncode == 0, proc.stdout + proc.stderr


class TestChangedScope:
    def _git(self, root, *args):
        return subprocess.run(["git", *args], cwd=root, text=True,
                              capture_output=True, timeout=60)

    def test_changed_scopes_to_diff_and_dependents(self, tmp_path):
        # helper.py gains a violation; caller.py (depends via the call
        # graph) and loner.py (violating but untouched and unrelated)
        # sit beside it. --changed must report helper's finding and
        # skip loner's.
        root = write_tree(tmp_path, {
            "pkg/helper.py": "def helper():\n    return 1\n",
            "pkg/caller.py": "from pkg.helper import helper\n\n\n"
                             "def use():\n    return helper()\n",
            "pkg/loner.py": LOCK_BAD,
        })
        for args in (("init", "-q"), ("add", "-A"),
                     ("-c", "user.email=t@t", "-c", "user.name=t",
                      "commit", "-qm", "seed")):
            proc = self._git(root, *args)
            assert proc.returncode == 0, proc.stderr
        # Introduce a violation in helper.py only.
        with open(os.path.join(root, "pkg", "helper.py"), "w") as fh:
            fh.write(textwrap.dedent(RACE_BAD))
        proc = subprocess.run(
            CLI + [os.path.join(root, "pkg"), "--no-baseline",
                   "--changed"],
            cwd=root, env=CLI_ENV, text=True, capture_output=True,
            timeout=120)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "GL202" in proc.stdout          # changed file reported
        assert "loner.py" not in proc.stdout   # untouched: filtered
        assert "--changed" in proc.stdout      # scope note printed

    def test_changed_deleted_file_recheck_its_importers(self, tmp_path):
        # Deleting a module leaves no call-graph nodes to walk back
        # from; its former importers must still land in scope (their
        # edges just vanished — exactly when GL402/GL202 conclusions
        # can change).
        root = write_tree(tmp_path, {
            "pkg/helper.py": "def helper():\n    return 1\n",
            "pkg/caller.py": "from pkg.helper import helper\n\n\n"
                             + textwrap.dedent(RACE_BAD).replace(
                                 "class Worker", "class Caller"),
        })
        for args in (("init", "-q"), ("add", "-A"),
                     ("-c", "user.email=t@t", "-c", "user.name=t",
                      "commit", "-qm", "seed")):
            proc = self._git(root, *args)
            assert proc.returncode == 0, proc.stderr
        os.unlink(os.path.join(root, "pkg", "helper.py"))
        proc = subprocess.run(
            CLI + [os.path.join(root, "pkg"), "--no-baseline",
                   "--changed"],
            cwd=root, env=CLI_ENV, text=True, capture_output=True,
            timeout=120)
        # caller.py imported the deleted helper: its GL202 finding is
        # in scope even though caller.py itself is untouched.
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "caller.py" in proc.stdout

    def test_changed_scopes_gl701_through_reverse_deps(self, tmp_path):
        # The GL70x inventory is interprocedural: editing the MODULE
        # THAT DEFINES the jit entry must pull the scheduler file that
        # dispatches it (its reverse dependent) back into --changed
        # scope, or an edit to the model layer could silently invalidate
        # a publish conclusion.
        root = write_tree(tmp_path, {
            "pkg/model.py": """\
                import functools

                import jax


                @functools.partial(jax.jit, static_argnames=("n",))
                def plan_step(state, n):
                    return state
            """,
            "pkg/engine.py": """\
                from pkg.model import plan_step


                class Engine:
                    def _loop(self):
                        plan_step({}, 1)   # never published
            """,
        })
        for args in (("init", "-q"), ("add", "-A"),
                     ("-c", "user.email=t@t", "-c", "user.name=t",
                      "commit", "-qm", "seed")):
            proc = self._git(root, *args)
            assert proc.returncode == 0, proc.stderr
        # touch ONLY the model module
        with open(os.path.join(root, "pkg", "model.py"), "a") as fh:
            fh.write("\n\nEXTRA = 1\n")
        proc = subprocess.run(
            CLI + [os.path.join(root, "pkg"), "--no-baseline",
                   "--changed"],
            cwd=root, env=CLI_ENV, text=True, capture_output=True,
            timeout=120)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "GL701" in proc.stdout
        assert "engine.py" in proc.stdout

    def test_changed_clean_when_nothing_changed(self, tmp_path):
        root = write_tree(tmp_path, {"pkg/loner.py": LOCK_BAD})
        for args in (("init", "-q"), ("add", "-A"),
                     ("-c", "user.email=t@t", "-c", "user.name=t",
                      "commit", "-qm", "seed")):
            proc = self._git(root, *args)
            assert proc.returncode == 0, proc.stderr
        proc = subprocess.run(
            CLI + [os.path.join(root, "pkg"), "--no-baseline",
                   "--changed"],
            cwd=root, env=CLI_ENV, text=True, capture_output=True,
            timeout=120)
        # loner.py's finding exists but is out of scope: exit 0.
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# the shipped tree itself
# ---------------------------------------------------------------------------


class TestShippedTree:
    def test_package_has_zero_nonbaselined_findings(self):
        bl_path = os.path.join(REPO, "lint-baseline.json")
        baseline = Baseline.load(bl_path) if os.path.isfile(bl_path) \
            else None
        findings = lint_paths([PKG], baseline=baseline)
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_checked_in_baseline_entries_all_have_reasons(self):
        bl_path = os.path.join(REPO, "lint-baseline.json")
        if not os.path.isfile(bl_path):
            pytest.skip("no baseline checked in")
        bl = Baseline.load(bl_path)
        for e in bl.entries:
            assert e.get("reason", "").strip(), e
            assert "justify or fix" not in e["reason"], (
                "placeholder reason left in the checked-in baseline")

    def test_cli_exit_0_on_shipped_tree(self):
        proc = run_cli("generativeaiexamples_tpu/")
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_cli_gl70x_select_exit_0_on_shipped_tree(self):
        # ISSUE 19 acceptance gate: the multihost collective-safety
        # family passes the shipped tree with only baselined findings.
        proc = run_cli("generativeaiexamples_tpu/", "--select",
                       "GL701,GL702,GL703,GL704")
        assert proc.returncode == 0, proc.stdout + proc.stderr


class TestKernelHotPathMarkers:
    """PR 15 pin: the tree-kernel dispatchers and the fused-sampling
    tail carry `# graftlint: hot-path` markers the linter actually
    SEES — a host sync seeded into the real source of each marked
    function must fire GL401 (and the unseeded copy must not). If a
    refactor moves the marker off the def line, these fail before the
    coverage silently evaporates."""

    # (relative source path, unique anchor line inside the marked
    # function, sync statement seeded right BEFORE it)
    CASES = [
        # paged_tree_attention_dispatch (bf16 twin)
        ("serving/paged_attention_tree.py",
         "    from generativeaiexamples_tpu.serving.paged_attention "
         "import (\n        paged_tree_attention_reference)\n",
         "    jax.block_until_ready(q)\n"),
        # paged_tree_attention_int8_dispatch
        ("serving/paged_attention_tree.py",
         "    from generativeaiexamples_tpu.serving.paged_attention "
         "import (\n        paged_tree_attention_int8_reference_fused)\n",
         "    jax.block_until_ready(q)\n"),
        # sample_token_into (fused finish)
        ("serving/engine_model.py",
         "    tok = sample_token(logits, temperature, top_p, top_k, key,\n"
         "                       all_greedy, any_top_k, any_top_p)\n",
         "    jax.block_until_ready(last_tokens)\n"),
        # prefill_chunk_sample_step (fused chunk tail)
        ("serving/engine_model.py",
         "    tok0 = sample_token(chunk_last, temperature, top_p, top_k, "
         "key,\n                        *sampling_flags)\n",
         "    jax.block_until_ready(chunk_last)\n"),
    ]

    @pytest.mark.parametrize("case", range(4))
    def test_seeded_sync_fires_gl401(self, case, tmp_path):
        rel, anchor, sync = self.CASES[case]
        src = open(os.path.join(PKG, rel)).read()
        assert src.count(anchor) == 1, (
            f"anchor line no longer unique/present in {rel}; update "
            f"TestKernelHotPathMarkers.CASES")
        clean_root = write_tree(tmp_path / "clean", {"mod.py": src})
        gl401 = [f for f in lint_paths([clean_root]) if f.check == "GL401"]
        assert gl401 == [], [f.format() for f in gl401]
        seeded = src.replace(anchor, sync + anchor, 1)
        bad_root = write_tree(tmp_path / "seeded", {"mod.py": seeded})
        gl401 = [f for f in lint_paths([bad_root]) if f.check == "GL401"]
        assert len(gl401) == 1, [f.format() for f in gl401]
        assert "block_until_ready" in gl401[0].message


class TestMultihostSeamMarkers:
    """Multi-host pin: the addressable-shard fetch seams in
    serving/multihost.py (`fetch_replicated`, `fetch_addressable`) are
    the only sanctioned host readback/gather crossings of a
    cross-process engine, and each carries a `# graftlint: hot-path`
    marker the linter actually SEES: a host sync seeded into the real
    source of either seam fires GL401, and the unseeded copy is quiet
    (the seams' own `np.asarray(arr)` of a replicated/local value is
    deliberately outside the device-name heuristic)."""

    CASES = [
        # fetch_replicated: the replicated-fetch fast path
        ("serving/multihost.py",
         "    if arr.is_fully_addressable or arr.is_fully_replicated:\n",
         "    jax.block_until_ready(arr)\n"),
        # fetch_addressable: the local-shard assembly path
        ("serving/multihost.py",
         "    local = {}\n",
         "    jax.block_until_ready(arr)\n"),
    ]

    @pytest.mark.parametrize("case", range(2))
    def test_seeded_sync_fires_gl401(self, case, tmp_path):
        rel, anchor, sync = self.CASES[case]
        src = open(os.path.join(PKG, rel)).read()
        assert src.count(anchor) == 1, (
            f"anchor line no longer unique/present in {rel}; update "
            f"TestMultihostSeamMarkers.CASES")
        clean_root = write_tree(tmp_path / "clean", {"mod.py": src})
        gl401 = [f for f in lint_paths([clean_root]) if f.check == "GL401"]
        assert gl401 == [], [f.format() for f in gl401]
        seeded = src.replace(anchor, sync + anchor, 1)
        bad_root = write_tree(tmp_path / "seeded", {"mod.py": seeded})
        gl401 = [f for f in lint_paths([bad_root]) if f.check == "GL401"]
        assert len(gl401) == 1, [f.format() for f in gl401]
        assert "block_until_ready" in gl401[0].message


class TestLintScript:
    """scripts/lint.py --ruff: cleanly-absent ruff skips with 0; a
    PRESENT-but-broken ruff package (import machinery raises) exits 2
    instead of silently reporting the requested step as passing."""

    def _load(self):
        import importlib.util as iu
        spec = iu.spec_from_file_location(
            "lint_script_under_test",
            os.path.join(REPO, "scripts", "lint.py"))
        mod = iu.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_ruff_broken_package_import_exits_2(self, monkeypatch, capsys):
        import importlib.util
        mod = self._load()
        monkeypatch.setattr(mod.shutil, "which", lambda name: None)

        def broken(name):
            raise ImportError("broken ruff install")

        monkeypatch.setattr(importlib.util, "find_spec", broken)
        assert mod.run_ruff(["pkg"]) == 2
        assert "--ruff requested" in capsys.readouterr().err

    def test_ruff_cleanly_absent_skips_with_0(self, monkeypatch):
        import importlib.util
        mod = self._load()
        monkeypatch.setattr(mod.shutil, "which", lambda name: None)
        monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
        assert mod.run_ruff(["pkg"]) == 0


class TestMultihostGaugeSurfacing:
    """GL601 over the REAL EngineMetrics: the multi-host/planner gauges
    (`multihost_processes`, `planner_headroom_bytes`) are read by
    snapshot(), so an increment anywhere in the class stays quiet; if
    a refactor drops the snapshot rows, the same increment fires GL601
    naming both gauges — the linter, not just the metrics tests, pins
    the surfacing contract."""

    SEED = ("    def note_seeded(self):\n"
            "        self.multihost_processes += 1\n"
            "        self.planner_headroom_bytes += 1\n\n"
            "    def snapshot(self)")

    def _engine_src(self):
        src = open(os.path.join(PKG, "serving", "engine.py")).read()
        assert src.count("    def snapshot(self)") == 1
        return src.replace("    def snapshot(self)", self.SEED, 1)

    def test_surfaced_gauges_stay_quiet(self, tmp_path):
        root = write_tree(tmp_path, {"engine.py": self._engine_src()})
        gl601 = [f for f in lint_paths([root]) if f.check == "GL601"]
        assert gl601 == [], [f.format() for f in gl601]

    def test_dropping_snapshot_rows_fires(self, tmp_path):
        src = self._engine_src()
        for row in ('            "multihost_processes": '
                    'self.multihost_processes,\n',
                    '            "planner_headroom_bytes": '
                    'self.planner_headroom_bytes,\n'):
            assert src.count(row) == 1, row
            src = src.replace(row, "", 1)
        root = write_tree(tmp_path, {"engine.py": src})
        gl601 = [f for f in lint_paths([root]) if f.check == "GL601"]
        msgs = " ".join(f.message for f in gl601)
        assert "multihost_processes" in msgs, msgs
        assert "planner_headroom_bytes" in msgs, msgs
