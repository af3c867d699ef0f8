"""kme-lint: per-rule fixtures (one violating + one clean per rule ID),
baseline semantics, the lock rules on synthetic modules, the runtime
lockcheck recorder, the ctypes-boundary validators, and a self-run
asserting `kme-lint --gate` is clean on this repo against the
checked-in baseline."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from kme_tpu.analysis import (Finding, load_baseline, repo_root,
                              save_baseline, split_new)
from kme_tpu.analysis import lockcheck, lockgraph, rules

# ---------------------------------------------------------------------------
# rule fixtures: (rule id, path the scope tables key on, violating
# source, clean source). Each violating snippet must fire EXACTLY its
# rule; each clean one must produce no findings at all.

FIXTURES = [
    ("KME-H001", "kme_tpu/bridge/service.py", """
class MatchService:
    def _step_pipelined(self):
        out = self.dev_out.block_until_ready()
""", """
class MatchService:
    def _collect_one(self):
        out = self.dev_out.block_until_ready()
"""),
    ("KME-H001", "kme_tpu/runtime/seqsession.py", """
import numpy as np
class SeqSession:
    def submit(self, batch):
        host = np.asarray(self.dev_buf)
""", """
import numpy as np
class SeqSession:
    def collect(self):
        host = np.asarray(self.dev_buf)
"""),
    ("KME-H002", "kme_tpu/runtime/seqsession.py", """
class SeqSession:
    def _plan(self, msgs):
        self.journal_f.flush()
""", """
class SeqSession:
    def _fetch_outputs(self):
        self.journal_f.flush()
"""),
    # the stamped batch produce: a per-record flush coming back into
    # its loop gates; _flush_log_lines is the one sanctioned exit
    ("KME-H002", "kme_tpu/bridge/broker.py", """
class Broker:
    def produce_stamped(self, topic, records, epoch, seq0):
        for key, value in records:
            self.logfile.flush()
""", """
def _flush_log_lines(logfile, lines):
    logfile.write("".join(lines))
    logfile.flush()
class Broker:
    def produce_stamped(self, topic, records, epoch, seq0):
        _flush_log_lines(self.logfile, list(records))
"""),
    # the one stamped-run path (PR 44): a run is one buffer, and a
    # flush a record coming back into its bounded-topic walk gates
    ("KME-H002", "kme_tpu/bridge/broker.py", """
class Broker:
    def _produce_run(self, topic, buf, off, klen, epoch, seq0):
        for i in range(len(klen)):
            self.logfile.flush()
""", """
def _flush_log_lines(logfile, lines):
    logfile.write("".join(lines))
    logfile.flush()
class Broker:
    def _produce_run(self, topic, buf, off, klen, epoch, seq0):
        _flush_log_lines(self.logfile, [self.rows])
"""),
    # fetch_runs is fetch() for a caller that wants bytes: the same
    # clock seam
    ("KME-C001", "kme_tpu/bridge/broker.py", """
import time
class Broker:
    def fetch_runs(self, name, offset):
        t0 = time.monotonic()
""", """
import time
class Broker:
    def _segment_stats(self):
        t0 = time.monotonic()
"""),
    ("KME-D001", "kme_tpu/bridge/broker.py", """
import time
class Broker:
    def _load_topic(self, name):
        stamp = time.time()
""", """
import time
class Broker:
    def _segment_stats(self):
        stamp = time.time()
"""),
    ("KME-C001", "kme_tpu/bridge/broker.py", """
import time
class Broker:
    def fetch(self, name, offset):
        t0 = time.monotonic()
""", """
import time
class Broker:
    def _segment_stats(self):
        t0 = time.monotonic()
"""),
    ("KME-D002", "kme_tpu/telemetry/journal.py", """
import random
def iter_events(path):
    jitter = random.random()
""", """
import random
def write_events(path):
    jitter = random.random()
"""),
    ("KME-E001", "kme_tpu/telemetry/events.py", """
import uuid
def make_event(source, seq, kind, ts_us):
    return {"src": source, "seq": seq, "kind": kind,
            "id": uuid.uuid4().hex}
""", """
import uuid
def write_merged(events, path):
    tmp = path + uuid.uuid4().hex
"""),
    ("KME-E001", "kme_tpu/telemetry/events.py", """
import time
class EventLog:
    def emit(self, kind):
        fallback = time.time
""", """
import time
class EventLog:
    def flush(self):
        self._last_flush = time.time()
"""),
    ("KME-T001", "kme_tpu/engine/newkernel.py", """
import jax.numpy as jnp
def step(state, price):
    if jnp.sum(price) > 0:
        return state
""", """
import jax.numpy as jnp
def step(state, price):
    return jnp.where(jnp.sum(price) > 0, state, state + 1)
"""),
    ("KME-T002", "kme_tpu/ops/newop.py", """
import jax.numpy as jnp
def pad(n):
    return jnp.zeros((n,))
""", """
import jax.numpy as jnp
def pad(n):
    return jnp.zeros((n,), dtype=jnp.int32)
"""),
    ("KME-T003", "kme_tpu/engine/newkernel.py", """
import numpy as np
def widen(x):
    return x.astype(int)
""", """
import numpy as np
def widen(x):
    return x.astype(np.int32)
"""),
]


@pytest.mark.parametrize(
    "rule,relpath,bad,good",
    FIXTURES, ids=[f"{r}-{i}" for i, (r, *_              # noqa: E501
                                      ) in enumerate(FIXTURES)])
def test_rule_fires_on_violation_only(rule, relpath, bad, good):
    got = {f.rule for f in rules.analyze_file(relpath, bad)}
    assert got == {rule}, f"want exactly {{{rule}}}, got {got}"
    clean = rules.analyze_file(relpath, good)
    assert clean == [], [f.render() for f in clean]


def test_syntax_error_is_a_finding_not_a_crash():
    got = rules.analyze_file("kme_tpu/engine/broken.py", "def f(:\n")
    assert [f.rule for f in got] == ["KME-E000"]


def test_t002_positional_dtype_and_preserving_asarray_are_clean():
    src = """
import numpy as np
import jax.numpy as jnp
def f(existing):
    a = np.asarray(existing)          # dtype-preserving: clean
    b = jnp.asarray(1, jnp.int32)     # positional dtype: clean
    c = np.zeros(4, np.int32)         # positional dtype: clean
    d = jnp.asarray([1, 2])           # fresh literals, no dtype: BAD
    return a, b, c, d
"""
    got = rules.analyze_file("kme_tpu/ops/x.py", src)
    assert [(f.rule, "jnp.asarray" in f.message) for f in got] \
        == [("KME-T002", True)]


# ---------------------------------------------------------------------------
# lock rules on synthetic threaded modules


def _write_module(tmp_path, rel, src):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(src)
    return rel


def test_l001_lock_order_cycle(tmp_path):
    rel = _write_module(tmp_path, "m/cyc.py", """
import threading

class A:
    def __init__(self):
        self.l1 = threading.Lock()
        self.l2 = threading.Lock()
    def fwd(self):
        with self.l1:
            with self.l2:
                pass
    def rev(self):
        with self.l2:
            with self.l1:
                pass
""")
    got = lockgraph.analyze_modules(str(tmp_path), (rel,))
    assert [f.rule for f in got] == ["KME-L001"]
    assert "l1" in got[0].message and "l2" in got[0].message


def test_l001_clean_when_orders_agree(tmp_path):
    rel = _write_module(tmp_path, "m/ok.py", """
import threading

class A:
    def __init__(self):
        self.l1 = threading.Lock()
        self.l2 = threading.Lock()
    def fwd(self):
        with self.l1:
            with self.l2:
                pass
    def also_fwd(self):
        with self.l1:
            with self.l2:
                pass
""")
    assert lockgraph.analyze_modules(str(tmp_path), (rel,)) == []


def test_l001_cycle_through_held_call(tmp_path):
    rel = _write_module(tmp_path, "m/call.py", """
import threading

class A:
    def __init__(self):
        self.l1 = threading.Lock()
        self.l2 = threading.Lock()
    def fwd(self):
        with self.l1:
            self._inner()
    def _inner(self):
        with self.l2:
            pass
    def rev(self):
        with self.l2:
            with self.l1:
                pass
""")
    got = lockgraph.analyze_modules(str(tmp_path), (rel,))
    assert [f.rule for f in got] == ["KME-L001"]


def test_l002_unlocked_cross_thread_store(tmp_path):
    rel = _write_module(tmp_path, "m/race.py", """
import threading

class W:
    def __init__(self):
        self.lock = threading.Lock()
        self.n = 0
        threading.Thread(target=self._work).start()
    def _work(self):
        self.n += 1
    def bump(self):
        self.n += 2
""")
    got = lockgraph.analyze_modules(str(tmp_path), (rel,))
    assert [f.rule for f in got] == ["KME-L002"]
    assert "self.n" in got[0].message


def test_l002_clean_under_common_lock_and_ctor_only(tmp_path):
    rel = _write_module(tmp_path, "m/ok2.py", """
import threading

class W:
    def __init__(self):
        self.lock = threading.Lock()
        self.n = 0
        self._restore()               # ctor-only helper: exempt
        threading.Thread(target=self._work).start()
    def _restore(self):
        self.n = -1
    def _work(self):
        with self.lock:
            self.n += 1
    def bump(self):
        with self.lock:
            self.n += 2
    def bump_via_helper(self):
        with self.lock:
            self._locked_bump()
    def _locked_bump(self):
        self.n += 3                    # guaranteed-caller-held: clean
""")
    got = lockgraph.analyze_modules(str(tmp_path), (rel,))
    assert got == [], [f.render() for f in got]


def test_l002_condition_aliases_its_wrapped_lock(tmp_path):
    rel = _write_module(tmp_path, "m/cond.py", """
import threading

class W:
    def __init__(self):
        self.lock = threading.Lock()
        self.data = threading.Condition(self.lock)
        self.n = 0
        threading.Thread(target=self._work).start()
    def _work(self):
        with self.data:
            self.n += 1
    def bump(self):
        with self.lock:
            self.n += 2
""")
    got = lockgraph.analyze_modules(str(tmp_path), (rel,))
    assert got == [], [f.render() for f in got]


# ---------------------------------------------------------------------------
# baseline semantics


def _mk(rule="KME-T002", path="kme_tpu/x.py", line=10, scope="f",
        snippet="a = jnp.zeros((4,))"):
    return Finding(rule=rule, path=path, line=line, col=0, scope=scope,
                   message="m", snippet=snippet)


def test_fingerprint_is_line_shift_stable():
    assert _mk(line=10).fingerprint == _mk(line=99).fingerprint
    assert _mk().fingerprint != _mk(rule="KME-T003").fingerprint
    assert _mk().fingerprint != _mk(snippet="b = 1").fingerprint


def test_baseline_roundtrip_and_gate_budget(tmp_path):
    base = str(tmp_path / "LINT_BASELINE.json")
    save_baseline(base, [_mk(), _mk(line=30)])   # same fp, count 2
    table = load_baseline(base)
    assert len(table) == 1
    (ent,) = table.values()
    assert ent["count"] == 2
    # two occurrences grandfathered, the third is new
    new, known = split_new([_mk(), _mk(line=30), _mk(line=50)], table)
    assert (len(new), len(known)) == (1, 2)
    # notes survive a rewrite
    table[_mk().fingerprint]["note"] = "accepted"
    with open(base, "w") as f:
        json.dump({"version": 1, "findings": table}, f)
    save_baseline(base, [_mk()])
    assert load_baseline(base)[_mk().fingerprint]["note"] == "accepted"


# ---------------------------------------------------------------------------
# runtime lockcheck


@pytest.fixture
def tracked_locks():
    lockcheck.install()
    lockcheck.reset()
    yield
    lockcheck.reset()
    lockcheck.uninstall()


def test_lockcheck_detects_inversion(tracked_locks):
    a, b = threading.Lock(), threading.Lock()

    def fwd():
        with a:
            with b:
                pass

    def rev():
        with b:
            with a:
                pass

    for fn in (fwd, rev):
        t = threading.Thread(target=fn)
        t.start()
        t.join()
    assert len(lockcheck.inversions()) == 1
    with pytest.raises(AssertionError):
        lockcheck.assert_clean()


def test_lockcheck_consistent_order_is_clean(tracked_locks):
    a, b = threading.Lock(), threading.Lock()
    for _ in range(3):
        with a:
            with b:
                pass
    assert lockcheck.inversions() == []
    lockcheck.assert_clean()


def test_lockcheck_condition_and_rlock(tracked_locks):
    lk = threading.Lock()
    cond = threading.Condition(lk)
    done = []

    def waiter():
        with cond:
            cond.wait(timeout=5)
            done.append(1)

    t = threading.Thread(target=waiter)
    t.start()
    # wait() must have released the tracked lock or this deadlocks
    import time
    time.sleep(0.1)
    with cond:
        cond.notify()
    t.join(timeout=5)
    assert done == [1]
    r = threading.RLock()
    with r:
        with r:          # reentry must not self-edge
            pass
    assert lockcheck.inversions() == []


def test_lockcheck_condition_over_default_rlock(tracked_locks):
    # Condition() wraps an RLock proxy: without a real _is_owned the
    # stdlib fallback (acquire(False)/release) reenters the owned
    # proxy, concludes not-owned, and wait() raises spuriously
    import time
    cond = threading.Condition()
    done = []

    def waiter():
        with cond:
            cond.wait(timeout=5)
            done.append(1)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)
    with cond:
        cond.notify()
    t.join(timeout=5)
    assert done == [1]


# ---------------------------------------------------------------------------
# ctypes boundary validators


def test_check_buffer_rejections():
    from kme_tpu.native import BoundaryError, check_buffer

    ok = np.zeros(8, np.int64)
    assert check_buffer("x", ok, np.int64, 8) is ok
    with pytest.raises(BoundaryError, match="dtype"):
        check_buffer("x", np.zeros(8, np.int32), np.int64, 8)
    with pytest.raises(BoundaryError, match="overread"):
        check_buffer("x", np.zeros(4, np.int64), np.int64, 8)
    with pytest.raises(BoundaryError, match="1-D"):
        check_buffer("x", np.zeros((2, 4), np.int64), np.int64)
    with pytest.raises(BoundaryError, match="contiguous"):
        check_buffer("x", np.zeros(16, np.int64)[::2], np.int64, 8)
    with pytest.raises(BoundaryError, match="ndarray"):
        check_buffer("x", [1, 2, 3], np.int64)


# ---------------------------------------------------------------------------
# self-run: the repo itself must gate clean against the baseline


def test_repo_gates_clean_against_baseline():
    root = repo_root()
    assert os.path.exists(os.path.join(root, "LINT_BASELINE.json"))
    proc = subprocess.run(
        [sys.executable, "-m", "kme_tpu.analysis.cli", "--gate",
         "--no-ruff"],
        capture_output=True, text=True, cwd=root, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_gate_fails_on_new_violation(tmp_path):
    root = repo_root()
    bad = tmp_path / "kme_tpu" / "engine" / "planted.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import jax.numpy as jnp\n"
                   "def f(n):\n"
                   "    return jnp.zeros((n,))\n")
    # path-scoped run, gated against the real baseline: the planted
    # violation is not grandfathered, so the gate must trip
    proc = subprocess.run(
        [sys.executable, "-m", "kme_tpu.analysis.cli", "--gate",
         "--no-ruff", str(bad)],
        capture_output=True, text=True, cwd=root, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "KME-T002" in proc.stdout
