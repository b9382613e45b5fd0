"""vearch-lint + lockcheck gate (static-analysis tentpole).

Two halves:

- the package gate: `python -m vearch_tpu.tools.lint vearch_tpu/`
  exits 0 against the checked-in allowlist — project invariants hold
  on every commit, in tier-1;
- planted-violation fixtures: each rule (and the runtime lock-order
  detector) demonstrably FIRES on seeded bad code, so a regression in
  the analyzer itself cannot silently turn the gate into a tautology.
"""

import os
import subprocess
import sys
import threading
import textwrap

import pytest

from vearch_tpu.tools import lockcheck
from vearch_tpu.tools.lint.core import Allowlist, run_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "vearch_tpu")


def _lint_file(tmp_path, rel, source, allowlist=None):
    """Write `source` at tmp_path/rel and lint it; returns unsuppressed
    findings. `rel` matters: path-suffix rules (VL102, VL302) key on it."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    findings = run_paths([str(path)], allowlist=allowlist)
    return [f for f in findings if not f.suppressed]


def _rules(findings):
    return sorted({f.rule for f in findings})


# -- the real gate -----------------------------------------------------------

def test_package_is_lint_clean():
    """The tree passes its own linter with the checked-in allowlist.
    Run as a subprocess so the exact CI/dev command is what's proven."""
    out = subprocess.run(
        [sys.executable, "-m", "vearch_tpu.tools.lint", PKG],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 finding(s)" in out.stdout


def test_list_rules_names_every_rule():
    out = subprocess.run(
        [sys.executable, "-m", "vearch_tpu.tools.lint", "--list-rules"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode == 0
    for rid in ("VL101", "VL102", "VL103", "VL104", "VL105", "VL201",
                "VL202", "VL203", "VL301", "VL302", "VL401", "VL501",
                "VL502", "VL503", "VL504"):
        assert rid in out.stdout, rid


# -- planted static violations: every rule fires -----------------------------

def test_vl101_hidden_dispatch_fires(tmp_path):
    found = _lint_file(tmp_path, "cluster/sneaky.py", """\
        import jax

        def warm(fn):
            return jax.jit(fn)
        """)
    assert _rules(found) == ["VL101"]


def test_vl101_silent_in_device_layers(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/ops/fine.py", """\
        import jax

        def warm(fn):
            return jax.jit(fn)
        """)
    assert found == []


def test_vl101_parallel_is_sanctioned_dispatch_layer(tmp_path):
    """The mesh data plane (parallel/) mints shard_map programs and
    jitted tail-append writers as a first-class dispatch layer."""
    found = _lint_file(tmp_path, "vearch_tpu/parallel/fine.py", """\
        import jax
        from jax import shard_map

        def build(mesh, specs, body):
            return jax.jit(shard_map(body, mesh=mesh, in_specs=specs,
                                     out_specs=specs[0]))
        """)
    assert found == []


def test_vl101_shard_map_outside_dispatch_layers_fires(tmp_path):
    """shard_map is a dispatch construct: the cluster plane minting one
    directly (instead of calling parallel/) still trips VL101."""
    found = _lint_file(tmp_path, "cluster/rogue_mesh.py", """\
        from jax import shard_map

        def scan(mesh, specs, body):
            return shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs[0])
        """)
    assert _rules(found) == ["VL101"]


def test_vl102_host_sync_in_serving_path_fires(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/ps.py", """\
        import numpy as np

        class PSServer:
            def _h_search(self, body):
                q = np.asarray(body["vectors"])
                return q

            def _h_other(self, body):
                return np.asarray(body)  # not a serving-path function
        """)
    # the lexical rule fires, and since ISSUE 20 the interprocedural
    # VL502 sees the same site (PSServer._h_search is a search entry)
    assert _rules(found) == ["VL102", "VL502"]
    assert {f.line for f in found} == {5}


def test_vl102_inline_allow_suppresses(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/ps.py", """\
        import numpy as np

        class PSServer:
            def _h_search(self, body):
                q = np.asarray(body["vectors"])  # lint: allow[host-sync] wire payload normalization
                return q
        """)
    assert found == []


def test_vl103_redeclared_tier_literal_fires(tmp_path):
    """Serving code minting its own shape grid (instead of importing the
    perf model's) breaks the zero-retrace bound silently — VL103."""
    found = _lint_file(tmp_path, "vearch_tpu/engine/rogue.py", """\
        MY_ROW_BUCKETS = (8, 32)

        def pick(n):
            return min(b for b in MY_ROW_BUCKETS if b >= n)
        """)
    assert _rules(found) == ["VL103"]
    assert "re-declare" in found[0].message


def test_vl103_import_and_inline_allow_pass(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/engine/fine.py", """\
        from vearch_tpu.ops import perf_model

        def pick(n):
            return perf_model.bucket_rows(n)

        HIST_BUCKETS = (1, 2, 4)  # lint: allow[bucket-drift] histogram bounds, not dispatch shapes
        """)
    assert found == []


def test_vl103_canonical_grid_must_match_policy_pin(tmp_path):
    """The perf model's own declaration is checked against the lint
    policy pin: a grid change must be a conscious two-file edit."""
    found = _lint_file(tmp_path, "vearch_tpu/ops/perf_model.py", """\
        ROW_BUCKETS = (8, 64, 256, 2048)
        FETCH_K_TIERS = (16, 64, 256, 1024)
        """)
    assert _rules(found) == ["VL103"]
    assert "policy" in found[0].message
    # matching grids are clean
    found = _lint_file(tmp_path, "vearch_tpu/ops/perf_model.py", """\
        ROW_BUCKETS = (8, 64, 256, 1024)
        FETCH_K_TIERS = (16, 64, 256, 1024)
        """)
    assert found == []
    # a missing declaration is as bad as a drifted one
    found = _lint_file(tmp_path, "vearch_tpu/ops/perf_model.py", """\
        ROW_BUCKETS = (8, 64, 256, 1024)
        """)
    assert _rules(found) == ["VL103"]
    assert "FETCH_K_TIERS" in found[0].message


def test_vl104_unattributed_billable_counter_fires(tmp_path):
    """A serving-path kill/shed counter incremented without naming the
    space un-attributes that failure class — VL104."""
    found = _lint_file(tmp_path, "vearch_tpu/cluster/ps.py", """\
        class PSServer:
            def shed(self, lbl):
                self._shed_total.inc("search")
                self._killed_total.inc("deadline", lbl)
        """)
    assert _rules(found) == ["VL104"]
    assert sorted(f.line for f in found) == [3, 4]
    assert "space" in found[0].message


def test_vl104_space_argument_passes(tmp_path):
    """Any space-shaped argument — a `space_lbl` local, a
    `_space_key()` call, a system-space constant — attributes the
    increment."""
    found = _lint_file(tmp_path, "vearch_tpu/cluster/ps.py", """\
        class PSServer:
            def shed(self, pid):
                space_lbl = self._acct.label(self._space_key(pid))
                self._shed_total.inc("search", space_lbl)
                self._killed_total.inc("deadline",
                                       self._space_key(pid))
                self._killed_total.inc("operator", SYSTEM_SPACE)
        """)
    assert found == []


def test_vl104_inline_allow_and_other_files_pass(tmp_path):
    """Genuinely tenant-free increments waive with a reason; the same
    code outside the serving files is out of scope."""
    found = _lint_file(tmp_path, "vearch_tpu/cluster/router.py", """\
        class Router:
            def warm(self):
                self._shed_total.inc(  # lint: allow[space-attr] zero-fill label registration
                    "search", "other", by=0.0)
        """)
    assert found == []
    found = _lint_file(tmp_path, "vearch_tpu/cluster/master.py", """\
        class Master:
            def note(self):
                self._shed_total.inc("search")
        """)
    assert found == []


def test_vl105_index_mutation_without_staleness_hook_fires(tmp_path):
    """A PS path that rebuilds an index without telling the quality
    monitor leaves shadow recall scoring fresh truth against the old
    serving snapshot — VL105."""
    found = _lint_file(tmp_path, "vearch_tpu/cluster/ps.py", """\
        class PSServer:
            def _run_build(self, pid, rebuild):
                eng = self.engines[pid]
                if rebuild:
                    eng.rebuild_index()
                else:
                    eng.build_index()
        """)
    assert _rules(found) == ["VL105"]
    assert len(found) == 1
    assert "note_index_mutation" in found[0].message


def test_vl105_hook_call_satisfies(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/ps.py", """\
        class PSServer:
            def _run_build(self, pid):
                self.engines[pid].build_index()
                self._quality.note_index_mutation(pid, "db/s", op="build")
        """)
    assert found == []


def test_vl105_other_files_out_of_scope_and_allow_waives(tmp_path):
    """The engine owns rebuild paths, so engine.py is IN scope (the
    config lists it next to ps.py); files outside the listed owners are
    not, and a justified def-line pragma waives in scope."""
    found = _lint_file(tmp_path, "vearch_tpu/engine/engine.py", """\
        class Engine:
            def absorb(self):
                self.index.build_index()
        """)
    assert _rules(found) == ["VL105"]
    found = _lint_file(tmp_path, "vearch_tpu/bench/warm.py", """\
        class Warmer:
            def absorb(self, eng):
                eng.build_index()
        """)
    assert found == []
    found = _lint_file(tmp_path, "vearch_tpu/cluster/ps.py", """\
        class PSServer:
            def _warm(self, eng):  # lint: allow[quality-staleness] offline warmup engine, never serves
                eng.build_index()
        """)
    assert found == []


def test_vl201_unguarded_mutation_fires(tmp_path):
    found = _lint_file(tmp_path, "store.py", """\
        import threading

        class Store:
            _guarded_by = {"items": "_lock", "count": "_lock"}

            def __init__(self):
                self._lock = threading.Lock()
                self.items = {}
                self.count = 0  # __init__ is exempt

            def good(self, k, v):
                with self._lock:
                    self.items[k] = v
                    self.count += 1

            def bad(self, k):
                self.items.pop(k, None)
                self.count -= 1
        """)
    assert _rules(found) == ["VL201"]
    assert len(found) == 2  # .pop() and the augmented assignment


def test_vl201_holds_pragma_trusted(tmp_path):
    found = _lint_file(tmp_path, "store.py", """\
        import threading

        class Store:
            _guarded_by = {"count": "_lock"}

            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def bump_locked(self):  # lint: holds[_lock]
                self.count += 1
        """)
    assert found == []


def test_vl202_anonymous_thread_fires(tmp_path):
    found = _lint_file(tmp_path, "bg.py", """\
        import threading

        def go(fn):
            threading.Thread(target=fn, daemon=True).start()
            threading.Thread(target=fn, daemon=True, name="ok").start()
        """)
    assert _rules(found) == ["VL202"]
    assert len(found) == 1 and found[0].line == 4


def test_vl203_wall_clock_fires_and_monotonic_passes(tmp_path):
    found = _lint_file(tmp_path, "timing.py", """\
        import time
        import time as _time

        def latency():
            t0 = time.time()
            t1 = _time.time()
            t2 = time.monotonic()
            return t0, t1, t2
        """)
    assert _rules(found) == ["VL203"]
    assert sorted(f.line for f in found) == [5, 6]


def test_vl301_bare_except_fires(tmp_path):
    found = _lint_file(tmp_path, "anything.py", """\
        def f():
            try:
                return 1
            except:
                return None
        """)
    assert _rules(found) == ["VL301"]


def test_vl302_swallowed_except_in_raft_fires(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/raft.py", """\
        def apply(entries, log):
            for e in entries:
                try:
                    e()
                except Exception:
                    pass
                try:
                    e()
                except Exception as exc:  # visible: logged
                    log.warning("apply failed: %s", exc)
        """)
    assert _rules(found) == ["VL302"]
    assert len(found) == 1 and found[0].line == 5


def test_vl302_only_in_critical_modules(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/router.py", """\
        def f(e):
            try:
                e()
            except Exception:
                pass
        """)
    assert found == []


def test_reasonless_pragma_is_itself_a_finding(tmp_path):
    found = _lint_file(tmp_path, "timing.py", """\
        import time

        def f():
            return time.time()  # lint: allow[wall-clock]
        """)
    # the naked pragma suppresses the site but fails the gate itself:
    # VL000 is unsuppressable, so a reasonless waiver still exits 1
    assert _rules(found) == ["VL000"]
    assert "no reason" in found[0].message


def test_allowlist_suppresses_and_unused_entries_fail(tmp_path):
    allow = tmp_path / "allow.txt"
    allow.write_text(
        "VL203 cluster/timing.py fixture proves file-scoped suppression\n"
        "VL101 cluster/gone.py this entry matches nothing\n"
    )
    found = _lint_file(tmp_path, "cluster/timing.py", """\
        import time

        def f():
            return time.time()
        """, allowlist=Allowlist(str(allow)))
    # VL203 suppressed by the first entry; the dead entry is a VL000
    assert _rules(found) == ["VL000"]
    assert "unused allowlist entry" in found[0].message


# -- runtime lockcheck: the dynamic half -------------------------------------

@pytest.fixture
def lockcheck_on():
    lockcheck.reset()
    lockcheck.enable()
    yield
    lockcheck.reset()


def test_make_lock_is_plain_when_disabled():
    lockcheck.reset()
    lockcheck.disable()
    try:
        lk = lockcheck.make_lock("x")
        assert not isinstance(lk, lockcheck.DebugLock)
    finally:
        lockcheck.reset()


def test_lock_order_inversion_detected(lockcheck_on):
    a = lockcheck.make_lock("fixture.a")
    b = lockcheck.make_lock("fixture.b")
    with a:
        with b:
            pass
    # reverse order on another thread — no deadlock is ever hit, the
    # edge graph alone proves the interleaving exists
    def reverse():
        with b:
            with a:
                pass
    t = threading.Thread(target=reverse, daemon=True, name="fixture-rev")
    t.start()
    t.join(timeout=10)
    kinds = [v["kind"] for v in lockcheck.violations()]
    assert "lock-order-inversion" in kinds
    with pytest.raises(AssertionError, match="lock-order-inversion"):
        lockcheck.check()


def test_consistent_order_is_clean(lockcheck_on):
    a = lockcheck.make_lock("fixture.c")
    b = lockcheck.make_lock("fixture.d")
    for _ in range(3):
        with a:
            with b:
                pass
    lockcheck.check()
    assert (("fixture.c", "fixture.d")
            in lockcheck.acquisition_edges())


def test_unguarded_write_detected(lockcheck_on):
    @lockcheck.guarded
    class Store:
        _guarded_by = {"count": "_lock"}

        def __init__(self):
            self._lock = lockcheck.make_lock("fixture.store")
            self.count = 0  # construction is exempt

    s = Store()
    with s._lock:
        s.count = 1  # guarded: fine
    s.count = 2  # seeded violation
    kinds = [v["kind"] for v in lockcheck.violations()]
    assert kinds == ["unguarded-write"]
    assert "Store.count" in lockcheck.violations()[0]["detail"]


def test_non_reentrant_reacquire_detected(lockcheck_on):
    lk = lockcheck.make_lock("fixture.plain", reentrant=False)
    with lk:
        with lk:  # a real Lock would deadlock right here
            pass
    kinds = [v["kind"] for v in lockcheck.violations()]
    assert "self-deadlock" in kinds


def test_foreign_release_detected(lockcheck_on):
    lk = lockcheck.make_lock("fixture.foreign", reentrant=True)
    lk.acquire()
    err: list[Exception] = []

    def releaser():
        try:
            lk.release()
        except Exception as e:  # RLock may refuse; the record is the point
            err.append(e)

    t = threading.Thread(target=releaser, daemon=True,
                         name="fixture-foreign")
    t.start()
    t.join(timeout=10)
    kinds = [v["kind"] for v in lockcheck.violations()]
    assert "foreign-release" in kinds


def test_condition_integration_keeps_held_stack_honest(lockcheck_on):
    lk = lockcheck.make_lock("fixture.cv", reentrant=True)
    cv = threading.Condition(lk)
    ready = threading.Event()

    def waiter():
        with cv:
            ready.set()
            cv.wait(timeout=10)
            # back under the lock after wait: guarded writes here must
            # see the lock as held
            assert lk.held_by_current()

    t = threading.Thread(target=waiter, daemon=True, name="fixture-cv")
    t.start()
    assert ready.wait(timeout=10)
    with cv:
        cv.notify_all()
    t.join(timeout=10)
    lockcheck.check()


# -- interprocedural rules (VL501-504): planted fixtures ---------------------
#
# Fixture files live under a fake vearch_tpu/ tree so the entry-point
# policy (path-suffix + qualname) matches them exactly like the real
# package; each fixture is linted ALONE, so the whole-program analysis
# is the fixture's own call graph.

def test_vl501_laundered_dispatch_fires_through_two_hops(tmp_path):
    """An inline allow[dispatch] waiver silences VL101 at the site, but
    the site is reachable from a search handler two hops up — VL501
    reports it with the full chain."""
    found = _lint_file(tmp_path, "vearch_tpu/cluster/router.py", """\
        import jax

        class RouterServer:
            def _h_search(self, body, parts):
                return self._route(body)

            def _route(self, body):
                return _prep(body)

        def _prep(body):
            fn = jax.jit(lambda x: x)  # lint: allow[dispatch] offline tooling claim
            return fn(body)
        """)
    assert "VL501" in _rules(found), found
    assert "VL101" not in _rules(found)  # the lexical waiver held
    msg = next(f for f in found if f.rule == "VL501").message
    assert "_h_search" in msg and "_route" in msg and "_prep" in msg


def test_vl502_blocking_open_three_frames_deep(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/router.py", """\
        class RouterServer:
            def _h_search(self, body, parts):
                return self._impl(body)

            def _impl(self, body):
                return _load(body)

        def _load(body):
            with open("/tmp/x") as f:
                return f.read()
        """)
    assert "VL502" in _rules(found), found
    msg = next(f for f in found if f.rule == "VL502").message
    assert "_h_search" in msg and "_impl" in msg and "_load" in msg


def test_vl502_pragma_at_offending_frame_suppresses(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/router.py", """\
        class RouterServer:
            def _h_search(self, body, parts):
                return _load(body)

        def _load(body):
            # lint: allow[serving-blocking] fixture: startup-only manifest read
            with open("/tmp/x") as f:
                return f.read()
        """)
    assert "VL502" not in _rules(found), found


def test_vl502_pragma_at_entry_does_not_launder(tmp_path):
    """The justification must sit at the offending frame; waiving the
    entry point does nothing for a callee's blocking call."""
    found = _lint_file(tmp_path, "vearch_tpu/cluster/router.py", """\
        class RouterServer:
            def _h_search(self, body, parts):  # lint: allow[serving-blocking] fixture: wrong frame
                return _load(body)

        def _load(body):
            with open("/tmp/x") as f:
                return f.read()
        """)
    assert "VL502" in _rules(found), found


def test_vl503_constructed_lock_cycle_fires(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/fixlocks.py", """\
        from vearch_tpu.tools import lockcheck

        _a = lockcheck.make_lock("fix.a")
        _b = lockcheck.make_lock("fix.b")

        def one():
            with _a:
                with _b:
                    pass

        def two():
            with _b:
                with _a:
                    pass
        """)
    assert "VL503" in _rules(found), found
    msg = next(f for f in found if f.rule == "VL503").message
    assert "fixlocks:_a" in msg and "fixlocks:_b" in msg


def test_vl503_consistent_order_is_clean(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/fixlocks.py", """\
        from vearch_tpu.tools import lockcheck

        _a = lockcheck.make_lock("fix.a")
        _b = lockcheck.make_lock("fix.b")

        def one():
            with _a:
                with _b:
                    pass

        def two():
            with _a:
                with _b:
                    pass
        """)
    assert "VL503" not in _rules(found), found


def test_vl503_transitive_acquire_completes_cycle(tmp_path):
    """a->b direct in one function, b->a only THROUGH a callee that
    takes _a while _b is held: the fixpoint closes the cycle."""
    found = _lint_file(tmp_path, "vearch_tpu/cluster/fixlocks.py", """\
        from vearch_tpu.tools import lockcheck

        _a = lockcheck.make_lock("fix.a")
        _b = lockcheck.make_lock("fix.b")

        def one():
            with _a:
                with _b:
                    pass

        def _inner():
            with _a:
                pass

        def two():
            with _b:
                _inner()
        """)
    assert "VL503" in _rules(found), found


def test_vl504_dropped_deadline_rpc_fires(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/router.py", """\
        from vearch_tpu.cluster import rpc

        class RouterServer:
            def _h_search(self, body, parts):
                return self._scatter(body)

            def _scatter(self, body):
                return rpc.call("addr", "POST", "/ps/doc/search", body)
        """)
    assert "VL504" in _rules(found), found
    msg = next(f for f in found if f.rule == "VL504").message
    assert "_h_search" in msg and "_scatter" in msg


def test_vl504_timeout_kwarg_or_body_deadline_satisfies(tmp_path):
    found = _lint_file(tmp_path, "vearch_tpu/cluster/router.py", """\
        from vearch_tpu.cluster import rpc

        class RouterServer:
            def _h_search(self, body, parts):
                rpc.call("addr", "POST", "/p", body, timeout=1.0)
                return rpc.call("addr", "POST", "/p",
                                {"deadline_ms": body.get("deadline_ms")})
        """)
    assert "VL504" not in _rules(found), found


def test_vl50x_out_of_reach_code_is_silent(tmp_path):
    """The same blocking call in a function NO entry point reaches
    produces no interprocedural finding (VL101 still applies lexically
    to dispatch, but offline open()/rpc are fine)."""
    found = _lint_file(tmp_path, "vearch_tpu/cluster/offline.py", """\
        from vearch_tpu.cluster import rpc

        def backup_tool(path):
            with open(path) as f:
                return rpc.call("addr", "POST", "/admin", f.read())
        """)
    assert not {"VL501", "VL502", "VL504"} & set(_rules(found)), found


# -- callgraph unit coverage -------------------------------------------------

def _analysis(tmp_path, files):
    from vearch_tpu.tools.lint import callgraph
    from vearch_tpu.tools.lint.core import FileContext

    ctxs = []
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        ctxs.append(FileContext(str(p), p.read_text()))
    return callgraph.build(ctxs)


def test_callgraph_resolves_self_methods_and_nested_defs(tmp_path):
    a = _analysis(tmp_path, {"vearch_tpu/cluster/router.py": """\
        class RouterServer:
            def _h_search(self, body, parts):
                def timed():
                    return self._deep(body)
                return timed()

            def _deep(self, body):
                return body
        """})
    reach = {q.split(":", 1)[1] for q in a.reachable("search")}
    assert "RouterServer._h_search.timed" in reach  # closure rule
    assert "RouterServer._deep" in reach            # self.m() via MRO


def test_callgraph_resolves_cross_module_imports(tmp_path):
    a = _analysis(tmp_path, {
        "vearch_tpu/cluster/router.py": """\
            from vearch_tpu.cluster import helpers
            from vearch_tpu.cluster.helpers import direct

            class RouterServer:
                def _h_search(self, body, parts):
                    helpers.work(body)
                    return direct(body)
            """,
        "vearch_tpu/cluster/helpers.py": """\
            def work(body):
                return body

            def direct(body):
                return body
            """,
    })
    reach = {q.split(":", 1)[1] for q in a.reachable("search")}
    assert "work" in reach and "direct" in reach


def test_callgraph_stoplisted_names_land_in_unresolved_bucket(tmp_path):
    a = _analysis(tmp_path, {"vearch_tpu/cluster/router.py": """\
        class RouterServer:
            def _h_search(self, body, parts):
                handle = body["h"]
                return handle.get("x")
        """})
    fn = next(f for f in a.funcs.values()
              if f.qualname == "RouterServer._h_search")
    kinds = {r.kind for r in fn.calls}
    assert "fanout" not in kinds  # "get" is stoplisted
    assert any(r.kind == "dynamic" and (r.dotted or "").endswith(".get")
               for r in fn.calls)


def test_lock_graph_artifact_coverage_semantics(tmp_path):
    """Wildcard/prefix lock nodes cover runtime names: the f-string
    mint `ps.flush{pid}` must cover a runtime `ps.flush3` edge."""
    from vearch_tpu.tools.lint import callgraph

    a = _analysis(tmp_path, {"vearch_tpu/cluster/fix.py": """\
        from vearch_tpu.tools import lockcheck

        class PS:
            def __init__(self):
                self._lock = lockcheck.make_lock("fix.ps._lock")
                self._fl = {}

            def _flush_lock(self, pid):
                with self._lock:
                    return self._fl.setdefault(
                        pid, lockcheck.make_lock(f"fix.ps.flush{pid}"))

            def flush(self, pid):
                with self._flush_lock(pid):
                    with self._lock:
                        pass
        """})
    art = a.lock_graph_artifact()
    assert art["cycles"] == []
    assert callgraph.edge_covered(art, "fix.ps.flush3", "fix.ps._lock")
    assert not callgraph.edge_covered(art, "fix.ps._lock", "other.lock")


def test_callgraph_types_locals_from_return_annotation(tmp_path):
    """`node = self._node(pid)` with `_node -> RaftNode` types the
    local; `self.nodes: dict[int, RaftNode]` types subscripted reads.
    Both make the lock the callee takes order under the holder."""
    from vearch_tpu.tools.lint import callgraph

    a = _analysis(tmp_path, {"vearch_tpu/cluster/fixann.py": """\
        from vearch_tpu.tools import lockcheck

        class RaftNode:
            def __init__(self):
                self._apply_lock = lockcheck.make_lock("fixann.apply")

            def save(self):
                with self._apply_lock:
                    pass

        class PS:
            def __init__(self):
                self._flush = lockcheck.make_lock("fixann.flush")
                self.nodes: dict[int, RaftNode] = {}

            def _node(self, pid) -> RaftNode:
                return self.nodes[pid]

            def flush(self, pid):
                node = self._node(pid)
                with self._flush:
                    node.save()
                    self.nodes[pid].save()
        """})
    fn = next(f for f in a.funcs.values() if f.qualname == "PS.flush")
    saves = [r for r in fn.calls
             if any(t.endswith("RaftNode.save") for t in r.targets)]
    assert len(saves) == 2
    assert all(r.kind == "resolved" for r in saves)
    art = a.lock_graph_artifact()
    assert callgraph.edge_covered(art, "fixann.flush", "fixann.apply")


def test_callgraph_ctor_injected_callback_orders_at_invocation(tmp_path):
    """Raft's apply_fn pattern: a lambda bound through the constructor
    resolves at the dynamic `self.apply_fn(...)` site, so the lock the
    callback takes orders under what the INVOKER holds there."""
    from vearch_tpu.tools.lint import callgraph

    a = _analysis(tmp_path, {"vearch_tpu/cluster/fixcb.py": """\
        from vearch_tpu.tools import lockcheck

        class Node:
            def __init__(self, apply_fn):
                self._lock = lockcheck.make_lock("fixcb.node")
                self.apply_fn = apply_fn

            def commit(self, op):
                with self._lock:
                    self.apply_fn(op)

        class PS:
            def __init__(self):
                self._stats = lockcheck.make_lock("fixcb.stats")
                self.node = Node(apply_fn=lambda op: self._apply(op))

            def _apply(self, op):
                with self._stats:
                    return op
        """})
    art = a.lock_graph_artifact()
    assert art["cycles"] == []
    assert callgraph.edge_covered(art, "fixcb.node", "fixcb.stats")
    fn = next(f for f in a.funcs.values() if f.qualname == "Node.commit")
    assert any(r.kind == "callback" for r in fn.calls)


def test_callgraph_param_callback_through_factory(tmp_path):
    """The hbm fetch pattern: a factory-made closure passed as an
    argument binds to the callee's param, so the closure's transitive
    locks order under what the callee holds at the `fetch(...)` site."""
    from vearch_tpu.tools.lint import callgraph

    a = _analysis(tmp_path, {"vearch_tpu/index/fixpc.py": """\
        from vearch_tpu.tools import lockcheck

        class Cache:
            def __init__(self):
                self._lock = lockcheck.make_lock("fixpc.cache")

            def resolve(self, fetch):
                with self._lock:
                    return fetch(1)

        class Tier:
            def __init__(self):
                self._lock = lockcheck.make_lock("fixpc.tier")

            def get(self, b):
                with self._lock:
                    return b

        class Index:
            def __init__(self):
                self.cache = Cache()
                self.tier = Tier()

            def _make_fetch(self):
                def fetch(b):
                    return self.tier.get(b)
                return fetch

            def lookup(self):
                fetch = self._make_fetch()
                return self.cache.resolve(fetch)
        """})
    art = a.lock_graph_artifact()
    assert art["cycles"] == []
    assert callgraph.edge_covered(art, "fixpc.cache", "fixpc.tier")


def test_callback_binding_does_not_launder_reachability(tmp_path):
    """Param/attr callback bindings are a global union; reachability
    must stay with the call-site deferred edges or one entry's
    callbacks would surface on another entry's serving path."""
    a = _analysis(tmp_path, {"vearch_tpu/cluster/router.py": """\
        class Retrier:
            def __init__(self):
                self.op = None

            def run(self, op):
                self.op = op
                return self.op()

        class RouterServer:
            def _h_search(self, body, parts):
                r = Retrier()
                return r.run(self._search_impl)

            def _h_upsert(self, body, parts):
                r = Retrier()
                return r.run(self._upsert_impl)

            def _search_impl(self):
                return 1

            def _upsert_impl(self):
                return 2
        """})
    search = {q.split(":", 1)[1] for q in a.reachable("search")}
    assert "RouterServer._search_impl" in search   # call-site deferred
    assert "RouterServer._upsert_impl" not in search  # no laundering


def test_doctor_lint_clean_standing_check():
    """The doctor's lint_clean invariant: in-process full-suite run is
    green over this tree and memoized for repeat doctor calls."""
    from vearch_tpu.obs import doctor

    ok, detail = doctor._check_lint_clean()
    assert ok is True, detail
    assert "0 hard finding" in detail
    assert doctor._check_lint_clean() == (ok, detail)  # memoized


# -- perf + CLI gates --------------------------------------------------------

def test_whole_package_single_parse_under_budget(monkeypatch):
    """ISSUE 20 perf gate: one lint pass over the package parses each
    file exactly once (the interprocedural analysis shares the lexical
    rules' contexts) and finishes well under the 30s budget."""
    import time as _time

    from vearch_tpu.tools.lint import core

    parses = []
    real = core.FileContext

    class Counting(real):
        def __init__(self, path, source):
            parses.append(path)
            super().__init__(path, source)

    monkeypatch.setattr(core, "FileContext", Counting)
    t0 = _time.monotonic()
    run_paths([PKG])
    elapsed = _time.monotonic() - t0
    assert elapsed < 30.0, f"package lint took {elapsed:.1f}s"
    assert parses, "no files parsed?"
    dup = {p for p in parses if parses.count(p) > 1}
    assert not dup, f"files parsed more than once: {sorted(dup)[:5]}"


def test_cli_json_and_lock_graph_modes():
    import json as _json

    out = subprocess.run(
        [sys.executable, "-m", "vearch_tpu.tools.lint", PKG, "--json"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    doc = _json.loads(out.stdout)
    assert doc["hard"] == 0 and doc["findings"] == []

    out = subprocess.run(
        [sys.executable, "-m", "vearch_tpu.tools.lint", PKG,
         "--lock-graph"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    art = _json.loads(out.stdout)
    assert art["cycles"] == []
    assert art["edges"], "the real tree has known lock nestings"
    ids = {n["id"] for n in art["nodes"]}
    for e in art["edges"]:
        assert e["first"] in ids and e["then"] in ids


def test_cli_changed_only_filters_to_diffed_files():
    """--changed-only HEAD with a clean tree reports nothing; against
    the empty tree it reports the same totals as a full run. Use a
    bogus ref to exercise the error path deterministically."""
    out = subprocess.run(
        [sys.executable, "-m", "vearch_tpu.tools.lint", PKG,
         "--changed-only", "no-such-ref-xyzzy"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert out.returncode == 2
    assert "cannot diff" in out.stderr
