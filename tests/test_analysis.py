"""Tests for thinvids_tpu.analysis — the repo-native static analyzer.

Two layers:

1. fixture mini-packages that each seed ONE violation class and
   assert the exact finding code (the analyzer must catch what it
   claims to catch);
2. the clean-tree gates: `run_all` over the real package yields no
   unwaived finding, and `cli.py check` (the tier-1 entry) exits 0 on
   HEAD — the analyzer is self-hosting, since thinvids_tpu.analysis is
   part of the tree it scans AND of the manifest's jax-free set.
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from thinvids_tpu.analysis import (Manifest, SourceTree, apply_waivers,
                                   default_manifest, run_all)
from thinvids_tpu.analysis import (configcheck, imports, jitcheck,
                                   statemachine, syncs, threads)
from thinvids_tpu.analysis.astutil import matches_any
from thinvids_tpu.analysis.manifest import StateMachine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_DIR = os.path.join(REPO, "thinvids_tpu")


def make_pkg(tmp_path, files, name="fixpkg"):
    root = tmp_path / name
    root.mkdir(exist_ok=True)
    files = dict(files)
    files.setdefault("__init__.py", "")
    for rel, src in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return SourceTree(str(root), package=name)


def codes(findings):
    return sorted(f.code for f in findings)


# ---------------------------------------------------------------------------
# pass 1: jax confinement + forbidden symbols
# ---------------------------------------------------------------------------


class TestImportsPass:
    def test_transitive_jax_leak(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "a.py": "from . import b\n",
            "b.py": "import jax\n",
        })
        m = Manifest(package="fixpkg", jax_free=("fixpkg.a",))
        found = imports.run(tree, m)
        assert codes(found) == ["TVT-J001"]
        assert "fixpkg.b" in found[0].message

    def test_package_init_edge_counts(self, tmp_path):
        # importing fixpkg.sub.mod executes fixpkg.sub.__init__, which
        # eagerly imports jax — the closure must include it
        tree = make_pkg(tmp_path, {
            "sub/__init__.py": "import jax\n",
            "sub/mod.py": "x = 1\n",
        })
        m = Manifest(package="fixpkg", jax_free=("fixpkg.sub.mod",))
        assert codes(imports.run(tree, m)) == ["TVT-J001"]

    def test_lazy_function_import_is_clean(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "a.py": "def f():\n    import jax\n    return jax\n",
        })
        m = Manifest(package="fixpkg", jax_free=("fixpkg.a",))
        assert imports.run(tree, m) == []

    def test_type_checking_import_is_clean(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "a.py": "from typing import TYPE_CHECKING\n"
                    "if TYPE_CHECKING:\n    import jax\n",
        })
        m = Manifest(package="fixpkg", jax_free=("fixpkg.a",))
        assert imports.run(tree, m) == []

    def test_cyclic_init_imports_terminate_with_chain(self, tmp_path):
        """Regression: a package-__init__ import cycle alongside a jax
        leak used to hang the chain reconstruction (merged per-root
        BFS parent maps could contain a cycle); the single multi-root
        traversal must terminate and still report the leak."""
        tree = make_pkg(tmp_path, {
            "sub/__init__.py": "from . import helper\n"
                               "from .. import xmod\n"
                               "from .. import jmod\n",
            "sub/helper.py": "x = 1\n",
            "sub/mod.py": "from .. import xmod\n",
            "xmod.py": "from .sub import helper\n",
            "jmod.py": "import jax\n",
        })
        m = Manifest(package="fixpkg", jax_free=("fixpkg.sub.mod",))
        found = imports.run(tree, m)
        assert codes(found) == ["TVT-J001"]
        assert "fixpkg.jmod" in found[0].message

    def test_forbidden_symbol(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "exec.py": "from .decode import read_video\n"
                       "def go(p):\n    return read_video(p)\n",
            "decode.py": "def read_video(p):\n    return []\n",
        })
        m = Manifest(package="fixpkg", jax_free=(),
                     forbidden_symbols={
                         "fixpkg.exec": (("read_video", "stream it"),)})
        found = imports.run(tree, m)
        assert codes(found) == ["TVT-J002"]
        assert "read_video" in found[0].message


# ---------------------------------------------------------------------------
# pass 2: host-sync confinement
# ---------------------------------------------------------------------------


class TestSyncsPass:
    def test_device_get_outside_allowlist(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "hot.py": "import jax\n"
                      "def f(x):\n    return jax.device_get(x)\n",
        })
        m = Manifest(package="fixpkg", sync_allowlist=())
        assert codes(syncs.run(tree, m)) == ["TVT-S001"]

    def test_allowlisted_module_is_clean(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "hot.py": "import jax\n"
                      "def f(x):\n    return jax.device_get(x)\n",
        })
        m = Manifest(package="fixpkg", sync_allowlist=("fixpkg.hot",))
        assert syncs.run(tree, m) == []

    def test_implicit_asarray_sync(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "hot.py": "import jax.numpy as jnp\nimport numpy as np\n"
                      "def f():\n"
                      "    x = jnp.zeros(8)\n"
                      "    return np.asarray(x)\n",
        })
        m = Manifest(package="fixpkg", sync_allowlist=())
        found = syncs.run(tree, m)
        assert codes(found) == ["TVT-S002"]

    def test_host_numpy_only_is_clean(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "cold.py": "import numpy as np\n"
                       "def f(y):\n"
                       "    x = np.ones(3)\n"
                       "    return np.asarray(x), float(y)\n",
        })
        m = Manifest(package="fixpkg", sync_allowlist=())
        assert syncs.run(tree, m) == []


# ---------------------------------------------------------------------------
# pass 3: thread-safety audit
# ---------------------------------------------------------------------------

_RACY = """
import threading

class Counter:
    def __init__(self):
        self.n = 0
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._loop)
        self._thread.start()

    def _loop(self):
        while True:
            self.n += 1

    def bump(self):
        self.n += 1
"""

_LOCKED = """
import threading

class Counter:
    def __init__(self):
        self.n = 0
        self._lock = threading.Lock()
        self._thread = None

    def start(self):
        self._thread = threading.Thread(target=self._loop)
        self._thread.start()

    def _loop(self):
        while True:
            with self._lock:
                self.n += 1

    def bump(self):
        with self._lock:
            self.n += 1
"""


class TestThreadsPass:
    def test_unlocked_cross_thread_write(self, tmp_path):
        tree = make_pkg(tmp_path, {"c.py": _RACY})
        found = threads.run(tree, Manifest(package="fixpkg"))
        assert codes(found) == ["TVT-T001"]
        assert "Counter.n" in found[0].message

    def test_locked_writes_are_clean(self, tmp_path):
        tree = make_pkg(tmp_path, {"c.py": _LOCKED})
        assert threads.run(tree, Manifest(package="fixpkg")) == []

    def test_pool_submit_alone_is_concurrent(self, tmp_path):
        tree = make_pkg(tmp_path, {"c.py": (
            "class Fan:\n"
            "    def __init__(self, pool):\n"
            "        self.pool = pool\n"
            "        self.done = 0\n"
            "    def go(self):\n"
            "        for _ in range(8):\n"
            "            self.pool.submit(self.work)\n"
            "    def work(self):\n"
            "        self.done += 1\n")})
        found = threads.run(tree, Manifest(package="fixpkg"))
        assert [f.code for f in found] == ["TVT-T001"]
        assert "Fan.done" in found[0].message

    def test_blocking_call_under_lock(self, tmp_path):
        tree = make_pkg(tmp_path, {"c.py": (
            "import threading, time\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def poke(self):\n"
            "        with self._lock:\n"
            "            time.sleep(1)\n")})
        found = threads.run(tree, Manifest(package="fixpkg"))
        assert codes(found) == ["TVT-T002"]

    def test_blocking_with_item_under_lock(self, tmp_path):
        """Regression: with-items' context expressions used to be
        invisible to the method visitor, so a context manager that
        blocks (`subprocess.Popen` as a `with` item) slipped past
        TVT-T002 — both in the combined `with lock, Popen()` form and
        nested inside a held lock."""
        tree = make_pkg(tmp_path, {"c.py": (
            "import threading, subprocess\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def combined(self, cmd):\n"
            "        with self._lock, subprocess.Popen(cmd) as p:\n"
            "            p.wait()\n"
            "    def nested(self, cmd):\n"
            "        with self._lock:\n"
            "            with subprocess.Popen(cmd) as p:\n"
            "                p.wait()\n")})
        found = threads.run(tree, Manifest(package="fixpkg"))
        assert codes(found) == ["TVT-T002", "TVT-T002"]

    def test_lock_order_inversion(self, tmp_path):
        tree = make_pkg(tmp_path, {"c.py": (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a_lock = threading.Lock()\n"
            "        self._b_lock = threading.Lock()\n"
            "    def ab(self):\n"
            "        with self._a_lock:\n"
            "            with self._b_lock:\n"
            "                pass\n"
            "    def ba(self):\n"
            "        with self._b_lock:\n"
            "            with self._a_lock:\n"
            "                pass\n")})
        found = threads.run(tree, Manifest(package="fixpkg"))
        assert "TVT-T003" in codes(found)

    def test_http_handler_classes_are_skipped(self, tmp_path):
        tree = make_pkg(tmp_path, {"h.py": (
            "from http.server import BaseHTTPRequestHandler\n"
            "class H(BaseHTTPRequestHandler):\n"
            "    def do_GET(self):\n"
            "        self.count = 1\n")})
        assert threads.run(tree, Manifest(package="fixpkg")) == []


# ---------------------------------------------------------------------------
# pass 4: config discipline
# ---------------------------------------------------------------------------


class TestConfigPass:
    DEFAULTS = {"used_key": 1, "dead_key": 2}

    def test_dead_key(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "app.py": "def f(snap):\n    return snap.used_key\n"})
        found = configcheck.run(tree, Manifest(package="fixpkg"),
                                defaults=self.DEFAULTS)
        assert codes(found) == ["TVT-C001"]
        assert "dead_key" in found[0].message

    def test_env_knobs(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "app.py": "import os\n"
                      "def f(snap):\n"
                      "    a = os.environ.get('TVT_BOGUS_KNOB')\n"
                      "    b = os.environ.get('MY_KNOB')\n"
                      "    c = os.environ.get('TVT_USED_KEY')\n"
                      "    d = os.environ.get('XLA_FLAGS')\n"
                      "    return a, b, c, d, snap.used_key, "
                      "snap.dead_key\n"})
        found = configcheck.run(tree, Manifest(package="fixpkg"),
                                defaults=self.DEFAULTS)
        assert codes(found) == ["TVT-C002", "TVT-C002"]
        details = sorted(f.key for f in found)
        assert details == ["TVT-C002:MY_KNOB", "TVT-C002:TVT_BOGUS_KNOB"]

    def test_raw_settings_subscript(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "app.py": "from .config import DEFAULT_SETTINGS\n"
                      "def f(settings):\n"
                      "    x = DEFAULT_SETTINGS['used_key']\n"
                      "    return x, settings.values['dead_key']\n",
            "config.py": "DEFAULT_SETTINGS = {}\n"})
        found = configcheck.check_raw_access(tree,
                                             Manifest(package="fixpkg"))
        assert codes(found) == ["TVT-C003", "TVT-C003"]


# ---------------------------------------------------------------------------
# pass 3b: guarded-by inference + cross-object lock order
# ---------------------------------------------------------------------------


class TestLocksetPass:
    def test_writes_under_different_locks(self, tmp_path):
        """TVT-T004a: both writers hold A lock — no, one holds _a_lock
        and one _b_lock; the lockset intersection is empty, so neither
        lock actually protects the field."""
        tree = make_pkg(tmp_path, {"s.py": (
            "import threading\n"
            "class S:\n"
            "    def __init__(self):\n"
            "        self._a_lock = threading.Lock()\n"
            "        self._b_lock = threading.Lock()\n"
            "        self.n = 0\n"
            "        self._thread = None\n"
            "    def start(self):\n"
            "        self._thread = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        with self._a_lock:\n"
            "            self.n += 1\n"
            "    def bump(self):\n"
            "        with self._b_lock:\n"
            "            self.n += 1\n")})
        found = threads.run(tree, Manifest(package="fixpkg"))
        assert codes(found) == ["TVT-T004"]
        assert "DIFFERENT locks" in found[0].message

    def test_consistent_single_lock_is_clean(self, tmp_path):
        tree = make_pkg(tmp_path, {"c.py": _LOCKED})
        assert threads.run(tree, Manifest(package="fixpkg")) == []

    def test_declared_guarded_by_read_without_lock(self, tmp_path):
        """TVT-T004b: a manifest-declared guarded field must hold its
        lock at EVERY read/write site (not just writes)."""
        tree = make_pkg(tmp_path, {"store.py": (
            "import threading\n"
            "class Store:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._jobs = {}\n"
            "    def put(self, k, v):\n"
            "        with self._lock:\n"
            "            self._jobs[k] = v\n"
            "    def peek(self):\n"
            "        return len(self._jobs)\n"
            "    def _find_locked(self, k):\n"
            "        return self._jobs.get(k)\n")})
        m = Manifest(package="fixpkg",
                     guarded_by={"fixpkg.store:Store._jobs": "_lock"})
        found = threads.run(tree, m)
        # peek() reads it unlocked; _find_locked is caller-holds-lock
        assert codes(found) == ["TVT-T004"]
        assert "peek" in found[0].message

    def test_cross_object_lock_cycle(self, tmp_path):
        """TVT-T005: Board holds its lock and calls into Manager
        (which takes _mgr_lock); Manager holds _mgr_lock and calls
        back into Board (which takes _lock) — a cross-object
        inversion, resolved through __init__ construction sites and
        parameter annotations."""
        tree = make_pkg(tmp_path, {"x.py": (
            "import threading\n"
            "class Board:\n"
            "    def __init__(self, mgr: 'Manager'):\n"
            "        self._lock = threading.Lock()\n"
            "        self.mgr = mgr\n"
            "    def poke(self):\n"
            "        with self._lock:\n"
            "            self.mgr.note()\n"
            "    def count(self):\n"
            "        with self._lock:\n"
            "            return 1\n"
            "class Manager:\n"
            "    def __init__(self):\n"
            "        self._mgr_lock = threading.Lock()\n"
            "        self.board = Board(self)\n"
            "    def note(self):\n"
            "        with self._mgr_lock:\n"
            "            pass\n"
            "    def drain(self):\n"
            "        with self._mgr_lock:\n"
            "            self.board.count()\n")})
        found = threads.run(tree, Manifest(package="fixpkg"))
        assert "TVT-T005" in codes(found)
        t5 = next(f for f in found if f.code == "TVT-T005")
        assert "cross-object" in t5.message

    def test_released_lock_does_not_fabricate_cross_edges(self, tmp_path):
        """Cross-object edges use the locks held AT the call site, not
        every lock the caller ever acquires: here _b_lock is acquired
        and RELEASED before the Manager call happens under _a_lock
        only, so there is no Board._b_lock → Manager._mgr_lock edge
        and no cycle with Manager's _mgr_lock → Board._b_lock path."""
        tree = make_pkg(tmp_path, {"z.py": (
            "import threading\n"
            "class Board:\n"
            "    def __init__(self, mgr: 'Manager'):\n"
            "        self._a_lock = threading.Lock()\n"
            "        self._b_lock = threading.Lock()\n"
            "        self.mgr = mgr\n"
            "    def poke(self):\n"
            "        with self._b_lock:\n"
            "            pass\n"
            "        with self._a_lock:\n"
            "            self._note_locked()\n"
            "    def _note_locked(self):\n"
            "        self.mgr.note()\n"
            "    def grab_b(self):\n"
            "        with self._b_lock:\n"
            "            return 1\n"
            "class Manager:\n"
            "    def __init__(self):\n"
            "        self._mgr_lock = threading.Lock()\n"
            "        self.board = Board(self)\n"
            "    def note(self):\n"
            "        with self._mgr_lock:\n"
            "            pass\n"
            "    def drain(self):\n"
            "        with self._mgr_lock:\n"
            "            self.board.grab_b()\n")})
        found = threads.run(tree, Manifest(package="fixpkg"))
        assert not [f for f in found
                    if f.code in ("TVT-T003", "TVT-T005")], \
            [f.format() for f in found]

    def test_same_named_classes_both_audited(self, tmp_path):
        """A second same-named class in one module (factory-local)
        must not shadow the first out of the audit: the top-level
        Worker's unlocked cross-thread write is still reported."""
        tree = make_pkg(tmp_path, {"w.py": (
            "import threading\n"
            "class Worker:\n"
            "    def __init__(self):\n"
            "        self.n = 0\n"
            "        self._thread = None\n"
            "    def start(self):\n"
            "        self._thread = threading.Thread(target=self._loop)\n"
            "    def _loop(self):\n"
            "        self.n += 1\n"
            "    def bump(self):\n"
            "        self.n += 1\n"
            "def make():\n"
            "    class Worker:\n"
            "        def quiet(self):\n"
            "            return 1\n"
            "    return Worker()\n")})
        found = threads.run(tree, Manifest(package="fixpkg"))
        assert "TVT-T001" in codes(found)
        assert any("Worker.n" in f.message for f in found)

    def test_guarded_read_and_write_keys_are_distinct(self, tmp_path):
        """One method that both reads AND writes a guarded field
        unlocked yields two findings under DIFFERENT waiver keys — one
        waiver must not silently suppress both debts."""
        tree = make_pkg(tmp_path, {"store.py": (
            "import threading\n"
            "class Store:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "        self._jobs = {}\n"
            "    def locked_put(self, k, v):\n"
            "        with self._lock:\n"
            "            self._jobs[k] = v\n"
            "    def swap(self, other):\n"
            "        old = self._jobs\n"
            "        self._jobs = other\n"
            "        return old\n")})
        m = Manifest(package="fixpkg",
                     guarded_by={"fixpkg.store:Store._jobs": "_lock"})
        found = threads.run(tree, m)
        t4 = [f for f in found if f.code == "TVT-T004"]
        assert len(t4) == 2
        assert len({f.key for f in t4}) == 2

    def test_local_alias_chain_is_followed(self, tmp_path):
        """`reg = self.co.registry; reg.beat()` under a held lock must
        contribute the same cross-object edge as the direct chain (the
        ShardBoard→WorkerRegistry shape)."""
        tree = make_pkg(tmp_path, {"y.py": (
            "import threading\n"
            "class Registry:\n"
            "    def __init__(self, board: 'Board'):\n"
            "        self._reg_lock = threading.Lock()\n"
            "        self.board = board\n"
            "    def beat(self):\n"
            "        with self._reg_lock:\n"
            "            pass\n"
            "    def scan(self):\n"
            "        with self._reg_lock:\n"
            "            self.board.depth()\n"
            "class Co:\n"
            "    def __init__(self, board: 'Board'):\n"
            "        self.registry = Registry(board)\n"
            "class Board:\n"
            "    def __init__(self, co: 'Co'):\n"
            "        self._lock = threading.Lock()\n"
            "        self.co = co\n"
            "    def claim(self):\n"
            "        with self._lock:\n"
            "            reg = self.co.registry\n"
            "            reg.beat()\n"
            "    def depth(self):\n"
            "        with self._lock:\n"
            "            return 0\n")})
        found = threads.run(tree, Manifest(package="fixpkg"))
        # Board._lock -> Registry._reg_lock via the LOCAL ALIAS
        # (`reg = self.co.registry; reg.beat()`), closed by scan()'s
        # direct `self.board.depth()` chain
        assert "TVT-T005" in codes(found)


# ---------------------------------------------------------------------------
# pass 5: protocol state machines (TVT-M001 audit + TVT-M002 model)
# ---------------------------------------------------------------------------

FIX_MACHINE = StateMachine(
    name="fix", enum="St", attr="state", scope=("fixpkg",),
    states=("A", "B", "C"), initial=("A",),
    transitions=(("A", "B"), ("B", "C")),
    predicates={"is_open": ("A", "B")})

_ST = "class St:\n    A = 'a'\n    B = 'b'\n    C = 'c'\n"


class TestStateMachineAudit:
    def manifest(self, machine=FIX_MACHINE):
        return Manifest(package="fixpkg", state_machines=(machine,))

    def test_unguarded_write_flags_undeclared_edges(self, tmp_path):
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "def f(o):\n"
            "    o.state = St.C\n")})
        found = statemachine.audit_transitions(tree, self.manifest())
        assert codes(found) == ["TVT-M001"]
        # B->C is declared; A->C and C->C are the undeclared sources
        assert "A" in found[0].message and "St.C" in found[0].message

    def test_is_guard_narrows_to_declared_edge(self, tmp_path):
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "def f(o):\n"
            "    if o.state is not St.A:\n"
            "        return\n"
            "    o.state = St.B\n"
            "def g(o):\n"
            "    if o.state is St.B:\n"
            "        o.state = St.C\n")})
        assert statemachine.audit_transitions(tree, self.manifest()) == []

    def test_predicate_guard_narrows(self, tmp_path):
        machine = dataclasses.replace(
            FIX_MACHINE, transitions=(("A", "C"), ("B", "C")))
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "def f(o):\n"
            "    if not o.state.is_open:\n"
            "        return\n"
            "    o.state = St.C\n")})
        assert statemachine.audit_transitions(
            tree, self.manifest(machine)) == []
        # without the guard, C->C is reachable and undeclared
        tree2 = make_pkg(tmp_path, {"m.py": _ST + (
            "def f(o):\n"
            "    o.state = St.C\n")}, name="fixpkg2")
        m2 = Manifest(package="fixpkg2", state_machines=(
            dataclasses.replace(machine, scope=("fixpkg2",)),))
        found = statemachine.audit_transitions(tree2, m2)
        assert codes(found) == ["TVT-M001"]

    def test_membership_guard_and_branches(self, tmp_path):
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "def f(o):\n"
            "    if o.state not in (St.A, St.B):\n"
            "        return\n"
            "    if o.state is St.A:\n"
            "        o.state = St.B\n"
            "    else:\n"
            "        o.state = St.C\n")})
        assert statemachine.audit_transitions(tree, self.manifest()) == []

    def test_setattr_write_site_is_audited(self, tmp_path):
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "def f(o):\n"
            "    setattr(o, 'state', St.B)\n")})
        found = statemachine.audit_transitions(tree, self.manifest())
        assert codes(found) == ["TVT-M001"]

    def test_lambda_write_site_is_audited(self, tmp_path):
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "def f(store, oid):\n"
            "    store.update(oid, lambda o: setattr(o, 'state', St.B))\n"
        )})
        found = statemachine.audit_transitions(tree, self.manifest())
        assert codes(found) == ["TVT-M001"]

    def test_loop_guard_with_continue(self, tmp_path):
        # the ShardBoard.report_failure shape: guard-exit inside a loop
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "def sweep(objs):\n"
            "    for o in objs:\n"
            "        if o.state is not St.B:\n"
            "            continue\n"
            "        o.state = St.C\n")})
        assert statemachine.audit_transitions(tree, self.manifest()) == []

    def test_bad_initial_default(self, tmp_path):
        # both the dataclass AnnAssign form and a plain class-body
        # Assign must hit the initial-state check
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "class Obj:\n"
            "    state: str = St.B\n"
            "class Obj2:\n"
            "    state = St.C\n")})
        found = statemachine.audit_transitions(tree, self.manifest())
        assert codes(found) == ["TVT-M001", "TVT-M001"]
        assert all("initial" in f.message for f in found)

    def test_annotated_assignment_is_audited(self, tmp_path):
        # `o.state: St = St.C` must not bypass the write audit
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "def f(o):\n"
            "    o.state: str = St.C\n")})
        found = statemachine.audit_transitions(tree, self.manifest())
        assert codes(found) == ["TVT-M001"]

    def test_dynamic_setattr_attr_name_is_audited(self, tmp_path):
        # a machine-enum VALUE written through a variable attribute
        # name is unauditable — treated as a write of the attr, so an
        # unguarded site still fails
        tree = make_pkg(tmp_path, {"m.py": _ST + (
            "def f(o, field):\n"
            "    setattr(o, field, St.C)\n")})
        found = statemachine.audit_transitions(tree, self.manifest())
        assert codes(found) == ["TVT-M001"]


class TestBoardModel:
    """TVT-M002: the bounded explorer over the ShardBoard model —
    clean on the declared table, and every seeded mutation produces a
    deterministic counterexample naming the violated invariant and
    the interleaving."""

    def test_clean_model_exercises_exactly_the_declared_table(self):
        m = default_manifest()
        violations, edges = statemachine.check_model(m)
        assert violations == []
        shard = next(mm for mm in m.state_machines
                     if mm.name == "shard")
        assert edges == set(shard.transitions)

    def test_model_findings_clean_on_declared_manifest(self):
        assert statemachine.model_findings(default_manifest()) == []

    def test_stale_table_is_a_finding(self):
        m = default_manifest()
        shard = next(mm for mm in m.state_machines
                     if mm.name == "shard")
        bloated = dataclasses.replace(
            shard, transitions=shard.transitions + (("DONE", "FAILED"),))
        m2 = dataclasses.replace(
            m, state_machines=(bloated,)
            + tuple(mm for mm in m.state_machines
                    if mm.name != "shard"))
        found = statemachine.model_findings(m2)
        assert codes(found) == ["TVT-M002"]
        assert "stale" in found[0].message

    def test_stale_worker_table_is_a_finding(self):
        """The drain scenario must exercise EXACTLY the declared
        worker-lifecycle table — a declared-but-impossible edge
        (ACTIVE→SUSPENDED skipping the drain) is a finding."""
        m = default_manifest()
        worker = next(mm for mm in m.state_machines
                      if mm.name == "worker")
        bloated = dataclasses.replace(
            worker,
            transitions=worker.transitions + (("ACTIVE", "SUSPENDED"),))
        m2 = dataclasses.replace(
            m, state_machines=tuple(
                mm for mm in m.state_machines if mm.name != "worker")
            + (bloated,))
        found = statemachine.model_findings(m2)
        assert codes(found) == ["TVT-M002"]
        assert "worker-lifecycle" in found[0].message
        assert "ACTIVE" in found[0].message

    def test_worker_model_exercises_exactly_the_declared_table(self):
        m = default_manifest()
        worker = next(mm for mm in m.state_machines
                      if mm.name == "worker")
        violations, _edges, wedges = statemachine._explore_all(
            m, None, (), statemachine.SCENARIOS)
        assert violations == []
        assert wedges == set(worker.transitions)

    @pytest.mark.parametrize("mutation,invariant", [
        ("double_assign", "single-assignment"),
        ("preempt_burns_attempt", "attempt-accounting"),
        ("accept_after_done", "done-absorbs"),
        ("no_token_fence", "token-fence"),
        ("collect_partial", "collect-all-done"),
        ("shared_ids", "cross-run-part"),
        ("no_expiry", "open-shard-unreachable"),
        ("gate_ignored", "qos-gate"),
        # worker-lifecycle machine (the elastic farm, ISSUE 12):
        # claims must never reach a DRAINING/SUSPENDED worker, and a
        # drain must never strand a lease by suspending under it
        ("claim_while_draining", "lifecycle-claim"),
        ("suspend_with_lease", "drain-strands-lease"),
        # durable checkpointing / crash-resume (ISSUE 13): a verified
        # spooled part must rehydrate DONE (never re-lease), resume
        # must not double-count attempts, and the two digest gates
        # (ingest + pre-stitch) must keep corrupt bytes out of DONE
        # shards and the stitched output
        ("resume_leases_done", "resume-reuse"),
        ("resume_burns_attempt", "attempt-accounting"),
        ("ingest_no_verify", "part-integrity"),
        ("stitch_no_verify", "part-integrity"),
        # band-group lockstep restart (farm SFE, ISSUE 14): a restart
        # that requeues a DONE sibling WITHOUT retracting its spooled
        # part re-leases work the spool already holds
        ("band_restart_keeps_spool", "resume-reuse"),
    ])
    def test_seeded_mutation_yields_counterexample(self, mutation,
                                                   invariant):
        violations, _ = statemachine.check_model(
            default_manifest(), mutations=(mutation,))
        assert violations, f"mutation {mutation} went undetected"
        v = violations[0]
        assert v.invariant == invariant
        # the counterexample names the interleaving
        assert "interleaving:" in v.format()
        assert v.trace

    def test_counterexample_is_deterministic(self):
        runs = [statemachine.check_model(default_manifest(),
                                         mutations=("shared_ids",))[0]
                for _ in range(2)]
        assert [(v.invariant, v.trace) for v in runs[0]] == \
            [(v.invariant, v.trace) for v in runs[1]]

    def test_unknown_mutation_rejected(self):
        with pytest.raises(ValueError):
            statemachine.BoardModel(statemachine.ModelConfig(),
                                    mutations=("bogus",))


# ---------------------------------------------------------------------------
# pass 6: jit/retrace discipline
# ---------------------------------------------------------------------------


class TestJitPass:
    def test_stray_jit_outside_declared_modules(self, tmp_path):
        tree = make_pkg(tmp_path, {"stray.py": (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x\n")})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=())
        found = jitcheck.run(tree, m)
        assert codes(found) == ["TVT-X001"]

    def test_unquantized_dynamic_slice_bound(self, tmp_path):
        tree = make_pkg(tmp_path, {"dev.py": (
            "def fetch(payload, used):\n"
            "    a = payload[:, :used.max()]\n"
            "    n = int(used.max())\n"
            "    b = payload[:, :n]\n"
            "    return a, b\n")})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=())
        found = jitcheck.run(tree, m)
        # one finding per function: both bounds are the same fix
        assert codes(found) == ["TVT-X001"]
        assert "quantizer" in found[0].message

    def test_taint_survives_unpack_and_annotated_assign(self, tmp_path):
        tree = make_pkg(tmp_path, {"dev.py": (
            "def a(payload, lens):\n"
            "    used, z = lens.max(), 0\n"
            "    return payload[:, :used]\n"
            "def b(payload, lens):\n"
            "    used: int = lens.max()\n"
            "    return payload[:, :used]\n")})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=())
        found = jitcheck.run(tree, m)
        assert codes(found) == ["TVT-X001", "TVT-X001"]

    def test_quantized_slice_is_clean(self, tmp_path):
        tree = make_pkg(tmp_path, {"dev.py": (
            "def fetch(payload, used, cut):\n"
            "    mu = cut(used.max())\n"
            "    return payload[:, :cut(used.max())], payload[:, :mu]\n"
        )})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=())
        assert jitcheck.run(tree, m) == []

    def test_nested_function_audited_once_with_own_taint(self, tmp_path):
        """A nested def is its own taint scope: the enclosing
        function's dynamic `used` must not leak into `inner`, whose
        parameter of the same name is an unknown (clean) value."""
        tree = make_pkg(tmp_path, {"dev.py": (
            "def outer(payload, lens):\n"
            "    used = lens.max()\n"
            "    def inner(payload, used):\n"
            "        return payload[:, :used]\n"
            "    return inner\n")})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=())
        assert jitcheck.run(tree, m) == []

    def test_static_shape_slices_are_clean(self, tmp_path):
        tree = make_pkg(tmp_path, {"dev.py": (
            "def stage(plane, mbh):\n"
            "    rows = mbh * 16\n"
            "    return plane[:rows, : plane.shape[1] // 2]\n")})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=())
        assert jitcheck.run(tree, m) == []

    def test_hot_loop_blocking_transfer(self, tmp_path):
        tree = make_pkg(tmp_path, {"dev.py": (
            "import jax\n"
            "class E:\n"
            "    def dispatch_wave(self, staged):\n"
            "        return jax.device_put(staged)\n"
            "    def stage_waves(self, frames):\n"
            "        return jax.device_put(frames)\n")})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=("fixpkg.dev:E.dispatch_wave",))
        found = jitcheck.run(tree, m)
        # stage_waves is an allowlisted transfer site (not declared
        # hot); only the dispatch-path device_put is flagged
        assert codes(found) == ["TVT-X002"]
        assert "dispatch_wave" in found[0].message

    def test_async_prefetch_is_legal_in_hot_loops(self, tmp_path):
        tree = make_pkg(tmp_path, {"dev.py": (
            "class E:\n"
            "    def dispatch_wave(self, out):\n"
            "        for arr in out:\n"
            "            arr.copy_to_host_async()\n"
            "        return out\n")})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=("fixpkg.dev:E.dispatch_wave",))
        assert jitcheck.run(tree, m) == []

    def test_plain_variable_named_item_is_not_a_transfer(self, tmp_path):
        # `.item()` is only a sync as an ATTRIBUTE call; an ordinary
        # loop variable named `item` must not trip TVT-X002
        tree = make_pkg(tmp_path, {"dev.py": (
            "class E:\n"
            "    def dispatch_wave(self, staged):\n"
            "        out = []\n"
            "        for item in staged:\n"
            "            out.append(item)\n"
            "        return out\n")})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=("fixpkg.dev:E.dispatch_wave",))
        assert jitcheck.run(tree, m) == []

    def test_same_named_methods_get_distinct_finding_keys(self, tmp_path):
        """GopShardEncoder.dispatch_wave vs SfeShardEncoder.
        dispatch_wave: same bare name, different classes — two
        findings under two waiver keys, not one swallowing the
        other."""
        tree = make_pkg(tmp_path, {"dev.py": (
            "class A:\n"
            "    def fetch(self, payload, used):\n"
            "        return payload[:, :used.max()]\n"
            "class B:\n"
            "    def fetch(self, payload, used):\n"
            "        return payload[:, :used.max()]\n")})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=())
        found = jitcheck.run(tree, m)
        assert codes(found) == ["TVT-X001", "TVT-X001"]
        assert len({f.key for f in found}) == 2

    def test_rotted_hot_loop_declaration_is_flagged(self, tmp_path):
        tree = make_pkg(tmp_path, {"dev.py": "x = 1\n"})
        m = Manifest(package="fixpkg", jit_modules=("fixpkg.dev",),
                     hot_loops=("fixpkg.dev:E.gone",))
        found = jitcheck.run(tree, m)
        assert codes(found) == ["TVT-X002"]
        assert "not found" in found[0].message


# ---------------------------------------------------------------------------
# output modes + stale-waiver enforcement (tools/check.py)
# ---------------------------------------------------------------------------


class TestCheckOutputs:
    def test_json_mode_carries_path_line_and_waiver_status(self, capsys):
        from thinvids_tpu.tools.check import run_check

        rc = run_check(json_out=True)
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert doc["open"] == []
        assert doc["stale_waivers"] == []
        assert doc["modules_scanned"] >= 70
        w = doc["waived"][0]
        assert w["waived"] is True and w["reason"]
        assert w["code"].startswith("TVT-") and w["key"]
        assert w["path"].endswith(".py")
        assert isinstance(w["line"], int) and w["line"] >= 1

    def test_sarif_mode_is_wellformed(self, capsys):
        from thinvids_tpu.tools.check import run_check

        rc = run_check(sarif_out=True)
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert all(r.startswith("TVT-") for r in rule_ids)
        results = run["results"]
        # HEAD is clean, so every result is a suppressed waiver
        assert results and all(r.get("suppressions") for r in results)
        for r in results:
            assert r["ruleId"] in rule_ids
            loc = r["locations"][0]["physicalLocation"]
            assert loc["artifactLocation"]["uri"]
            assert loc["region"]["startLine"] >= 1
            assert r["partialFingerprints"]["tvtKey"]

    def test_stale_waiver_fails_the_check(self, capsys, monkeypatch):
        import thinvids_tpu.analysis as analysis
        from thinvids_tpu.tools.check import run_check

        base = analysis.default_manifest()
        stale = dataclasses.replace(
            base, waivers={**dict(base.waivers),
                           "TVT-Z999:never-matches": "dead debt"})
        monkeypatch.setattr(analysis, "default_manifest", lambda: stale)
        rc = run_check(quiet=True)
        out = capsys.readouterr().out
        assert rc == 1
        assert "stale waiver" in out

    def test_precommit_hook_is_installable(self):
        hook = os.path.join(REPO, "deploy", "pre-commit")
        assert os.path.exists(hook)
        assert os.access(hook, os.X_OK)
        with open(hook, encoding="utf-8") as fh:
            body = fh.read()
        assert "cli check" in body or "cli.py check" in body \
            or "thinvids_tpu.cli check" in body
        assert "test_analysis" in body


# ---------------------------------------------------------------------------
# waivers
# ---------------------------------------------------------------------------


class TestWaivers:
    def test_waived_and_stale(self, tmp_path):
        tree = make_pkg(tmp_path, {
            "hot.py": "import jax\n"
                      "def f(x):\n    return jax.device_get(x)\n"})
        m = Manifest(package="fixpkg", sync_allowlist=(),
                     waivers={"TVT-S001:fixpkg.hot:device_get": "known",
                              "TVT-S001:fixpkg.gone:device_get": "old"})
        open_, waived, stale = apply_waivers(syncs.run(tree, m), m)
        assert open_ == []
        assert len(waived) == 1
        assert stale == ["TVT-S001:fixpkg.gone:device_get"]


# ---------------------------------------------------------------------------
# the clean-tree gates (tier-1)
# ---------------------------------------------------------------------------


class TestCleanTree:
    def test_run_all_clean_on_head(self):
        manifest = default_manifest()
        tree = SourceTree(PKG_DIR)
        open_, _waived, stale = apply_waivers(run_all(tree, manifest),
                                              manifest)
        assert not open_, "\n".join(f.format() for f in open_)
        assert not stale, f"stale waivers: {stale}"
        # the acceptance bar: the waiver list stays SHORT
        assert len(manifest.waivers) <= 5

    def test_cli_check_exits_zero_and_jax_free(self):
        """`cli.py check` joins tier-1: exits 0 on HEAD, runs without
        ever importing jax (it must stay fast enough to ride every
        test run)."""
        code = ("import sys\n"
                "from thinvids_tpu.tools.check import run_check\n"
                "rc = run_check(quiet=True)\n"
                "assert rc == 0, 'check found open findings'\n"
                "assert 'jax' not in sys.modules, 'check imported jax'\n")
        env = dict(os.environ,
                   PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        subprocess.run([sys.executable, "-c", code], check=True,
                       env=env, timeout=60)

    def test_jax_free_modules_import_without_jax_at_runtime(self):
        """Belt and braces for the static proof: actually import EVERY
        manifest-declared jax-free module in an interpreter where jax
        cannot load — catches dynamic imports (importlib, module-scope
        calls that lazily pull jax) the AST graph cannot see. The
        module list derives from the manifest, so new declarations are
        covered automatically."""
        manifest = default_manifest()
        tree = SourceTree(PKG_DIR)
        mods = [m for m in tree.modules()
                if matches_any(m, manifest.jax_free)]
        assert len(mods) >= 10      # io/*, abr, live, analysis, ...
        code = ("import sys\n"
                "sys.modules['jax'] = None\n"
                "sys.modules['jax.numpy'] = None\n"
                + "\n".join(f"import {m}" for m in mods)
                + "\nprint('ok')\n")
        env = dict(os.environ,
                   PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0 and "ok" in out.stdout, out.stderr

    def test_analyzer_is_self_hosting(self):
        """The analysis package is inside its own jax-free manifest,
        so every pass runs over the analyzer's own source."""
        manifest = default_manifest()
        assert matches_any("thinvids_tpu.analysis.threads",
                           manifest.jax_free)
        assert matches_any("thinvids_tpu.tools.check",
                           manifest.jax_free)
        tree = SourceTree(PKG_DIR)
        assert "thinvids_tpu.analysis.threads" in tree.modules()
