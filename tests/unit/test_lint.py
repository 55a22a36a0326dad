"""dstpu-lint analyzer suite (tools/lint, docs/lint.md).

Fixture snippets per rule family (positive AND negative cases), the
baseline round-trip, CLI exit codes, suppression markers — plus
regression tests pinning the true-positive findings this linter
surfaced in the runtime and that were FIXED rather than baselined:

  * slot_store.py  — NvmeSlotStore.flush/close mutating ring state
                     without the lock (LOCK001)
  * infinity.py    — per-microbatch ``float(loss)`` syncs serializing
                     the gas loop (SYNC002)
  * engine.py      — a fresh ``jax.jit(lambda ...)`` compiled every
                     ``backward`` call (TRACE003)
  * config.py      — raw/orphaned config keys (CFG001/CFG003)
"""
import json
import os
import textwrap

import pytest

from deepspeed_tpu.tools.lint import Baseline, lint_paths
from deepspeed_tpu.tools.lint.cli import main as lint_main
from deepspeed_tpu.tools.lint.rules_config import check_pytest_markers

pytestmark = pytest.mark.lint

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
PKG = os.path.join(REPO_ROOT, "deepspeed_tpu")


def run_lint(tmp_path, sources, **kw):
    """Write {relpath: source} under tmp_path and lint it."""
    for rel, src in sources.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return lint_paths([str(tmp_path)], root=str(tmp_path), **kw)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# SYNC family
# ---------------------------------------------------------------------------
def test_sync_item_and_float_in_jitted_fn(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import jax

        @jax.jit
        def step(x):
            y = x * 2
            bad = y.item()
            worse = float(compute(y))
            fine = float(len([1, 2]))
            return bad + worse + fine
        """})
    assert "SYNC001" in rules_of(fs)
    assert "SYNC002" in rules_of(fs)
    # severity: inside a jit these are errors
    assert all(f.severity == "error" for f in fs
               if f.rule in ("SYNC001", "SYNC002"))
    assert not any(f.detail.startswith("float:len")
                   for f in fs), "float(len(...)) is a host scalar"


def test_sync_cold_function_not_flagged(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        def export_params(x):
            return x.item()
        """})
    assert fs == []


def test_sync_step_name_and_callgraph_propagation(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import numpy as np

        def _fetch(arr):
            return np.asarray(arr)

        class Engine:
            def train_step(self, batch):
                return self._helper(batch)

            def _helper(self, batch):
                return _fetch(batch)
        """})
    syncs = [f for f in fs if f.rule == "SYNC003"]
    assert len(syncs) == 1 and syncs[0].scope == "_fetch"
    assert syncs[0].severity == "warning"  # step-hot, not jit-hot


def test_sync_host_transfer_whitelisted(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import numpy as np

        def host_transfer(value, block=False):
            return np.asarray(value)

        def train_step(batch):
            loss = run_program(batch)
            return float(host_transfer(loss))
        """})
    assert fs == []


def test_sync_block_until_ready_flagged(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import jax

        def train_step(batch):
            out = program(batch)
            jax.block_until_ready(out)
            return out
        """})
    assert rules_of(fs) == ["SYNC003"]


# ---------------------------------------------------------------------------
# TRACE family
# ---------------------------------------------------------------------------
def test_trace_branch_on_traced_value(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, mask):
            y = x + 1
            if y > 0:                 # traced -> TRACE001
                x = -x
            while mask:               # traced -> TRACE001
                break
            if x.shape[0] > 2:        # static projection: fine
                x = x[:2]
            if mask is None:          # identity test: fine
                mask = jnp.ones(())
            return x
        """})
    t1 = [f for f in fs if f.rule == "TRACE001"]
    assert sorted(f.detail for f in t1) == ["if:y", "while:mask"]


def test_trace_static_argnums_param_not_tainted(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        from functools import partial
        import jax

        @partial(jax.jit, static_argnums=(1,))
        def step(x, mode):
            if mode:                  # static arg: fine
                return x * 2
            return x
        """})
    assert [f for f in fs if f.rule == "TRACE001"] == []


def test_trace_impure_calls(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import time
        import numpy as np
        import jax

        @jax.jit
        def step(x, key):
            t = time.time()               # TRACE002
            n = np.random.rand()          # TRACE002
            ok = jax.random.uniform(key)  # functional: fine
            return x + t + n + ok
        """})
    t2 = sorted(f.detail for f in fs if f.rule == "TRACE002")
    assert t2 == ["np.random.rand", "time.time"]


def test_trace_retrace_bombs(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import jax

        def per_call(x):
            return jax.jit(lambda a: a * 2)(x)      # immediate call

        def per_iter(xs):
            out = []
            for x in xs:
                f = jax.jit(lambda a: a + 1)        # jit in loop
                out.append(f(x))
            return out

        _cached = jax.jit(lambda a: a - 1)          # module-level: fine

        def good(x):
            return _cached(x)
        """})
    t3 = sorted(f.detail for f in fs if f.rule == "TRACE003")
    assert t3 == ["immediate-call", "jit-in-loop"]


def test_trace_unhashable_static_arg(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import jax

        def f(x, cfg):
            return x

        g = jax.jit(f, static_argnums=(1,))

        def caller(x):
            bad = g(x, [1, 2])          # list is unhashable -> TRACE004
            ok = g(x, (1, 2))           # tuple is hashable
            return bad, ok
        """})
    t4 = [f for f in fs if f.rule == "TRACE004"]
    assert len(t4) == 1 and t4[0].detail == "g:1"


# ---------------------------------------------------------------------------
# LOCK family
# ---------------------------------------------------------------------------
def test_lock_unlocked_mutation_flagged(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import threading

        class Store:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def put(self, x):
                with self._lock:
                    self._items.append(x)

            def reset(self):
                self._items = []        # unlocked mutation -> LOCK001
        """})
    l1 = [f for f in fs if f.rule == "LOCK001"]
    assert len(l1) == 1
    assert l1[0].detail == "_items" and "reset" in l1[0].scope


def test_lock_locked_entry_private_method_clean(tmp_path):
    # the slot_store pattern: private helpers called only under the lock
    fs = run_lint(tmp_path, {"m.py": """\
        import threading

        class Store:
            def __init__(self):
                self._lock = threading.RLock()
                self._cond = threading.Condition(self._lock)
                self._state = {}

            def put(self, k, v):
                with self._lock:
                    self._mutate(k, v)

            def get(self, k):
                with self._cond:
                    return self._state.get(k)

            def _mutate(self, k, v):
                self._state[k] = v      # lock held by every caller
        """})
    assert [f for f in fs if f.rule == "LOCK001"] == []


def test_lock_order_inversion(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import threading

        class TwoLocks:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()
                self.n = 0

            def ab(self):
                with self._a:
                    with self._b:
                        self.n += 1

            def ba(self):
                with self._b:
                    with self._a:
                        self.n -= 1
        """})
    assert any(f.rule == "LOCK002" and f.detail == "_a<->_b" for f in fs)


def test_lock_thread_daemon_join(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import threading

        def fire_and_forget(fn):
            threading.Thread(target=fn).start()          # LOCK003

        def daemonized(fn):
            threading.Thread(target=fn, daemon=True).start()

        def joined(fn):
            t = threading.Thread(target=fn)
            t.start()
            t.join()
        """})
    l3 = [f for f in fs if f.rule == "LOCK003"]
    assert len(l3) == 1 and "fire_and_forget" not in l3[0].scope


# ---------------------------------------------------------------------------
# CFG family
# ---------------------------------------------------------------------------
CFG_FIXTURE = {
    "pkg/runtime/constants.py": """\
        USED_KEY = "used_key"
        ORPHAN_KEY = "orphan_key"
        USED_DEFAULT = 7
        ORPHAN_DEFAULT = 9
        """,
    "pkg/runtime/config.py": """\
        from . import constants as C

        class Config:
            def __init__(self, pd):
                g = pd.get
                self.used = g(C.USED_KEY, C.USED_DEFAULT)
                self.raw = g("mystery_key", None)
        """,
}


def test_cfg_orphans_and_raw_keys(tmp_path):
    fs = run_lint(tmp_path, CFG_FIXTURE)
    assert {(f.rule, f.detail) for f in fs} == {
        ("CFG001", "ORPHAN_KEY"),
        ("CFG002", "ORPHAN_DEFAULT"),
        ("CFG003", "mystery_key"),
    }


def test_cfg_marker_check(tmp_path):
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    good: a registered marker\n")
    tdir = tmp_path / "tests"
    tdir.mkdir()
    (tdir / "test_x.py").write_text(textwrap.dedent("""\
        import pytest

        @pytest.mark.good
        @pytest.mark.typo_marker
        @pytest.mark.parametrize("x", [1])
        def test_a(x):
            pass
        """))
    fs = check_pytest_markers(str(tmp_path))
    assert [f.detail for f in fs] == ["typo_marker"]
    assert fs[0].rule == "TEST001"


# ---------------------------------------------------------------------------
# suppression markers
# ---------------------------------------------------------------------------
def test_suppression_markers(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import numpy as np

        def train_step(batch):
            a = np.asarray(batch)  # dstpu: ignore[SYNC003] -- host data
            b = np.asarray(batch)  # dstpu: ignore
            # dstpu: ignore[SYNC003] -- marker on the line above
            c = np.asarray(batch)
            d = np.asarray(batch)  # dstpu: ignore[LOCK001] -- wrong rule
            return a, b, c, d
        """})
    assert len(fs) == 1 and fs[0].detail.endswith("batch")
    assert fs[0].line == 8  # only the wrong-rule marker line survives


def test_suppression_invalid_ids_do_not_blanket(tmp_path):
    """A typo'd rule id in the bracket must suppress NOTHING — never
    degrade to a blanket ignore-all (code-review finding)."""
    fs = run_lint(tmp_path, {"m.py": """\
        import numpy as np

        def train_step(batch):
            a = np.asarray(batch)  # dstpu: ignore[sync003] -- lowercase typo
            b = np.asarray(batch)  # dstpu: ignore[NOT A RULE]
            return a, b
        """})
    assert sorted(f.line for f in fs) == [4, 5]


def test_suppression_only_in_real_comments(tmp_path):
    """Marker text inside a docstring/string literal is documentation,
    not a suppression (the scanner reads COMMENT tokens only)."""
    fs = run_lint(tmp_path, {"m.py": '''\
        import numpy as np

        def train_step(batch):
            """Mentions # dstpu: ignore[SYNC003] in prose only."""
            s = "# dstpu: ignore"
            return np.asarray(batch), s
        '''})
    assert [f.rule for f in fs] == ["SYNC003"]


# ---------------------------------------------------------------------------
# baseline round-trip + CLI exit codes
# ---------------------------------------------------------------------------
HAZARD = {"m.py": """\
    import jax

    @jax.jit
    def step(x):
        return x.item()
    """}


def test_baseline_roundtrip(tmp_path):
    fs = run_lint(tmp_path, HAZARD)
    assert len(fs) == 1
    bl = Baseline.from_findings(fs)
    path = tmp_path / "baseline.json"
    bl.save(str(path))
    loaded = Baseline.load(str(path))
    new, old = loaded.split(fs)
    assert new == [] and len(old) == 1
    # an extra finding beyond the grandfathered count is new
    new2, old2 = loaded.split(fs + fs)
    assert len(new2) == 1 and len(old2) == 1
    # an empty baseline marks everything new
    assert Baseline({}).split(fs)[0] == fs


def test_baseline_rejects_garbage(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"not": "a baseline"}))
    with pytest.raises(ValueError):
        Baseline.load(str(p))


def test_cli_exit_codes(tmp_path, capsys):
    src = tmp_path / "pkg"
    src.mkdir()
    (src / "m.py").write_text(textwrap.dedent(HAZARD["m.py"]))
    root = str(tmp_path)
    bl = str(tmp_path / "lint_baseline.json")
    # findings, no baseline -> fail
    assert lint_main([str(src), "--root", root, "--no-baseline"]) == 1
    # write the baseline -> clean gate
    assert lint_main([str(src), "--root", root, "--write-baseline",
                      "--baseline", bl]) == 0
    assert lint_main([str(src), "--root", root, "--baseline", bl]) == 0
    # a NEW hazard beyond the baseline -> fail again
    (src / "n.py").write_text(textwrap.dedent("""\
        def train_step(b):
            return b.item()
        """))
    assert lint_main([str(src), "--root", root, "--baseline", bl]) == 1
    # usage errors
    assert lint_main([str(tmp_path / "missing"), "--root", root]) == 2
    # an explicit but missing baseline path is a usage error, not an
    # empty baseline (which would report everything as NEW)
    assert lint_main([str(src), "--root", root,
                      "--baseline", bl + ".typo"]) == 2
    # an unparsable file is unanalyzed coverage — it must fail the run,
    # not silently shrink it
    (src / "broken.py").write_text("def broken(:\n")
    assert lint_main([str(src), "--root", root, "--no-baseline"]) == 2
    (src / "broken.py").unlink()
    # a rule-filtered run must never overwrite the full baseline
    assert lint_main([str(src), "--root", root, "--rules", "SYNC",
                      "--write-baseline", "--baseline", bl]) == 2
    assert Baseline.load(bl).counts, "baseline was clobbered"
    out = capsys.readouterr().out
    assert "SYNC001" in out and "new" in out


def test_cli_json_format_and_list_rules(tmp_path, capsys):
    src = tmp_path / "pkg"
    src.mkdir()
    (src / "m.py").write_text(textwrap.dedent(HAZARD["m.py"]))
    assert lint_main([str(src), "--root", str(tmp_path), "--no-baseline",
                      "--format", "json"]) == 1
    data = json.loads(capsys.readouterr().out)
    assert data["new"][0]["rule"] == "SYNC001"
    assert data["new"][0]["line"] == 5
    assert lint_main(["--list-rules"]) == 0
    assert "LOCK002" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# regression: the true positives fixed in this PR stay fixed
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def repo_findings():
    return lint_paths([PKG], root=REPO_ROOT)


def test_repo_slot_store_lock_discipline(repo_findings):
    """NvmeSlotStore.flush/close used to mutate _buf_op/_bufs without
    the ring lock — fixed, must not regress."""
    assert [f for f in repo_findings
            if f.rule.startswith("LOCK")
            and f.path.endswith("slot_store.py")] == []


def test_repo_infinity_gas_loop_stays_lazy(repo_findings):
    """InfinityStepper.train_step used to float() every microbatch's
    loss/norm scalars inside the gas loop — gas-1 pipeline stalls per
    step. The scalars are now converted after the worker join."""
    assert [f for f in repo_findings
            if f.rule == "SYNC002"
            and f.scope == "InfinityStepper.train_step"] == []


def test_repo_engine_backward_jit_cached(repo_findings):
    """DeepSpeedEngine.backward used to build a fresh jax.jit(lambda)
    every call — a trace+compile per microbatch."""
    assert [f for f in repo_findings
            if f.rule == "TRACE003"
            and f.scope == "DeepSpeedEngine.backward"] == []


def test_repo_config_schema_consistent(repo_findings):
    """config.py parses no raw string keys and EVERY constant has a
    consumer — the MOE/ROUTE_* legacy orphans were deleted in PR 7, so
    any CFG001 here is a fresh schema lie, not grandfathered history."""
    assert [f for f in repo_findings if f.rule == "CFG003"] == []
    assert [f for f in repo_findings if f.rule == "CFG001"] == []
    assert not any(f.rule == "CFG002" for f in repo_findings)


def test_repo_markers_registered():
    assert check_pytest_markers(REPO_ROOT) == []


def test_repo_clean_against_committed_baseline(repo_findings):
    """The CI gate, as a test — PR 7 burned the baseline to ZERO by
    fixing (not suppressing) all 20 grandfathered findings, so the tree
    must be finding-free against an EMPTY baseline: the ratchet is
    fully tightened and any hazard fails here first."""
    bl = Baseline.load(os.path.join(REPO_ROOT, "lint_baseline.json"))
    assert bl.counts == {}, "baseline must stay empty — fix, don't add"
    new, _ = bl.split(repo_findings)
    assert new == [], "\n".join(f.render() for f in new)


def test_repo_true_positive_fixes_stay_fixed(repo_findings):
    """Regression pins for the PR 7 live-tree fixes: the offload step's
    scattered float() syncs now ride ONE batched host_transfer
    (SYNC002/SYNC003), the init/onebit jit builds are cached (TRACE003),
    every shard_map call routes through the compat shim (MESH004 —
    ring/ulysses were AttributeError-dead on the pinned jax), and the
    decode kernel streams ragged tails without a full-cache jnp.pad
    (PALLAS004)."""
    assert [f.render() for f in repo_findings
            if f.scope.endswith("_offload_train_step")
            or "shard_batch" in f.scope] == []
    assert [f.render() for f in repo_findings if f.rule == "TRACE003"] == []
    assert [f.render() for f in repo_findings if f.family == "MESH"] == []
    assert [f.render() for f in repo_findings if f.family == "PALLAS"] == []
    assert [f.render() for f in repo_findings if f.family == "LIFE"] == []


def test_repo_v3_families_clean(repo_findings):
    """The v3 rollout census was reconciled in-PR, not baselined: the
    frontend's active-tenant set is sorted (DET002), every replica
    state write is legal against _TRANSITIONS (FLEET), the metric /
    config docs tables match the registry and dataclasses, and every
    fault site is swept by a chaos matrix (DRIFT)."""
    assert [f.render() for f in repo_findings if f.family == "DET"] == []
    assert [f.render() for f in repo_findings if f.family == "FLEET"] == []
    assert [f.render() for f in repo_findings if f.family == "DRIFT"] == []


# ---------------------------------------------------------------------------
# functional regression for the slot_store fix
# ---------------------------------------------------------------------------
def test_slot_store_flush_close_under_concurrency(tmp_path):
    """flush()/close() now serialize against the ring lock: hammer a
    store with concurrent release/flush and verify slot contents."""
    import numpy as np
    from deepspeed_tpu.runtime.swap_tensor.slot_store import NvmeSlotStore

    store = NvmeSlotStore(8, 512, str(tmp_path / "s.swp"), buffer_count=3)
    try:
        for i in range(8):
            store.write_slot(i, np.full(512, i, np.uint8))
        import threading
        errs = []

        def writer():
            try:
                for i in range(8):
                    buf = store.acquire(i)
                    buf[:] = (i + 1) % 256
                    store.release(i, dirty=True)
            except Exception as e:  # pragma: no cover
                errs.append(e)

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        for _ in range(16):
            store.flush()
        t.join(30)
        assert not t.is_alive() and errs == []
        for i in range(8):
            assert store.read_slot(i)[0] == (i + 1) % 256
    finally:
        store.close()


def test_slot_store_close_waits_for_pins(tmp_path):
    """close() must not free buffers out from under an outstanding
    acquire (e.g. a peer parked in the retry backoff): it waits for the
    release, and raises on a genuine acquire/release imbalance."""
    import threading
    import time as _time
    import numpy as np
    from deepspeed_tpu.runtime.swap_tensor.slot_store import NvmeSlotStore

    store = NvmeSlotStore(2, 256, str(tmp_path / "p.swp"), buffer_count=2)
    store.write_slot(0, np.full(256, 7, np.uint8))
    buf = store.acquire(0)                    # pin held
    done = []

    def closer():
        store.close()
        done.append(True)

    t = threading.Thread(target=closer, daemon=True)
    t.start()
    _time.sleep(0.3)
    assert not done, "close() returned while a buffer was still acquired"
    assert buf[0] == 7                        # view still valid
    store.release(0)
    t.join(30)
    assert done and not t.is_alive()

    # a genuinely dangling pin: bounded wait, loud warning, then close
    # proceeds (teardown may run during exception cleanup — it must not
    # mask the original error by raising)
    store2 = NvmeSlotStore(2, 256, str(tmp_path / "q.swp"),
                           buffer_count=2)
    store2.CLOSE_PIN_WAIT_TIMEOUT = 0.3
    store2.acquire(0)
    t0 = _time.monotonic()
    store2.close()
    assert _time.monotonic() - t0 >= 0.3      # waited the full budget
    assert store2._bufs == []


# ---------------------------------------------------------------------------
# PALLAS family — kernel hazards (PR 7)
# ---------------------------------------------------------------------------
def test_pallas_retired_names(tmp_path):
    """Names the installed jax removed or deprecated are flagged; their
    replacements are clean."""
    fs = run_lint(tmp_path, {"ops/kern.py": """\
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def build():
            return pltpu.CompilerParams(dimension_semantics=("parallel",))

        def spec():
            return pl.BlockSpec(memory_space=pl.ANY)

        def build_old():
            return pltpu.TPUCompilerParams()

        def spec_old():
            return pl.BlockSpec(memory_space=pltpu.ANY)
        """})
    hits = [f for f in fs if f.rule == "PALLAS001"]
    assert sorted(f.detail for f in hits) == ["ANY", "TPUCompilerParams"]
    assert all(f.severity == "error" for f in hits)


def test_pallas_select_by_multiply(tmp_path):
    """The PR 6 NaN-leak class: mask * v in a kernel is flagged; the
    jnp.where form (and plain prob-times-value products) are not."""
    fs = run_lint(tmp_path, {"ops/kern.py": """\
        import jax
        import jax.numpy as jnp

        def _kernel(len_ref, q_ref, v_ref, o_ref):
            pos = jax.lax.broadcasted_iota(jnp.int32, (8, 4), 0)
            mask = pos < len_ref[0]
            v = v_ref[...]
            bad = mask * v                    # select-by-multiply
            worse = v * (pos < len_ref[0])    # inline comparison
            probs = jnp.exp(v)
            fine = probs * v                  # not a mask product
            good = jnp.where(mask, v, 0.0)
            o_ref[...] = bad + worse + fine + good
        """})
    hits = [f for f in fs if f.rule == "PALLAS002"]
    assert len(hits) == 2 and all(f.severity == "error" for f in hits)
    assert sorted(h.detail for h in hits) == [
        "mult:mask", "mult:pos < len_ref[0]"]


def test_pallas_select_by_multiply_only_in_kernels(tmp_path):
    """MoE gating etc. legitimately multiplies by masks OUTSIDE kernels
    — the rule scopes to pallas kernel functions (>=2 *_ref params or
    passed to pallas_call)."""
    fs = run_lint(tmp_path, {"moe.py": """\
        import jax.numpy as jnp

        def gate(scores, k):
            mask = scores > 0
            return scores * mask
        """})
    assert [f for f in fs if f.rule == "PALLAS002"] == []


def test_pallas_scratch_dtype(tmp_path):
    fs = run_lint(tmp_path, {"ops/kern.py": """\
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def _kernel(x_ref, o_ref, acc):
            o_ref[...] = x_ref[...]

        def wrapper(x):
            return pl.pallas_call(
                _kernel,
                grid=(1,),
                scratch_shapes=[pltpu.VMEM((8, 128), jnp.bfloat16)],
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

        def wrapper_ok(x):
            return pl.pallas_call(
                _kernel,
                grid=(1,),
                scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
        """})
    hits = [f for f in fs if f.rule == "PALLAS003"]
    assert len(hits) == 1 and hits[0].detail == "bfloat16"


def test_pallas_pad_in_wrapper(tmp_path):
    fs = run_lint(tmp_path, {"ops/kern.py": """\
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def wrapper(x):
            x = jnp.pad(x, ((0, 3),))
            return pl.pallas_call(
                _kernel, grid=(1,),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

        def elsewhere(x):
            return jnp.pad(x, ((0, 3),))   # not a kernel wrapper: fine
        """})
    hits = [f for f in fs if f.rule == "PALLAS004"]
    assert len(hits) == 1 and hits[0].scope == "wrapper"


def test_pallas_index_map_hazards(tmp_path):
    fs = run_lint(tmp_path, {"ops/kern.py": """\
        import time
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        class K:
            def build(self, block):
                def bad_state(i, p, len_ref):
                    return (self.offset + i, 0)    # mutable capture

                def bad_host(i, p, len_ref):
                    return (int(time.time()) + i, 0)

                def good(i, p, len_ref):
                    last = jnp.maximum(len_ref[i] // block - 1, 0)
                    return (jnp.minimum(p, last), 0)

                return [pl.BlockSpec((1, block), bad_state),
                        pl.BlockSpec((1, block), bad_host),
                        pl.BlockSpec((1, block), good)]
        """})
    hits = [f for f in fs if f.rule == "PALLAS005"]
    assert {h.scope for h in hits} == {"bad_state", "bad_host"}
    assert not any(h.scope == "good" for h in hits)


# ---------------------------------------------------------------------------
# MESH family — sharding discipline (PR 7)
# ---------------------------------------------------------------------------
_TOPO_FIXTURE = """\
    AXIS_ORDER = ("dcn_data", "pipe", "data", "expert", "sequence",
                  "model")
    DATA_AXIS = "data"
    MODEL_AXIS = "model"
    """


def test_mesh_explicit_specs_required(tmp_path):
    fs = run_lint(tmp_path, {
        "parallel/topology.py": _TOPO_FIXTURE,
        "m.py": """\
        from deepspeed_tpu.parallel.shard_map_compat import shard_map

        def good(f, mesh, spec):
            return shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec)

        def bad(f, mesh):
            return shard_map(f, mesh=mesh)
        """})
    hits = [f for f in fs if f.rule == "MESH001"]
    assert len(hits) == 1 and hits[0].scope == "bad"


def test_mesh_undeclared_axis_literal(tmp_path):
    fs = run_lint(tmp_path, {
        "parallel/topology.py": _TOPO_FIXTURE,
        "m.py": """\
        import jax

        def body(x):
            good = jax.lax.psum(x, "data")
            also = jax.lax.pmean(x, axis_name="model")
            bad = jax.lax.psum(x, "bogus_axis")
            idx = jax.lax.axis_index("sequnce")   # typo'd
            return good + also + bad + idx
        """})
    hits = sorted(f.detail for f in fs if f.rule == "MESH002")
    assert hits == ["axis_index:sequnce", "psum:bogus_axis"]


def test_mesh_no_topology_module_stays_silent(tmp_path):
    """Without a parallel/topology.py the declared-axis set is unknown —
    the rule must not guess."""
    fs = run_lint(tmp_path, {"m.py": """\
        import jax

        def body(x):
            return jax.lax.psum(x, "whatever")
        """})
    assert [f for f in fs if f.rule == "MESH002"] == []


def test_mesh_ctor_outside_topology(tmp_path):
    fs = run_lint(tmp_path, {
        "parallel/topology.py": _TOPO_FIXTURE + """\

    def build_mesh(devices):
        from jax.sharding import Mesh
        return Mesh(devices, AXIS_ORDER)   # the one blessed site
    """,
        "m.py": """\
        from jax.sharding import Mesh

        def sneaky(devices):
            return Mesh(devices, ("data",))

        def hardcoded(d0, d1):
            return Mesh([d0, d1], ("data",))
        """})
    hits = {f.scope: f for f in fs if f.rule == "MESH003"}
    assert set(hits) == {"sneaky", "hardcoded"}
    assert hits["sneaky"].severity == "warning"
    assert hits["hardcoded"].severity == "error"


def test_mesh_shard_map_compat_bypass(tmp_path):
    """Direct jax.shard_map use AND shard_map imports from jax are
    flagged; the in-tree wrapper import is the fix."""
    fs = run_lint(tmp_path, {
        "parallel/topology.py": _TOPO_FIXTURE,
        "a.py": """\
        import jax

        def f(body, mesh, spec):
            return jax.shard_map(body, mesh=mesh, in_specs=spec,
                                 out_specs=spec)
        """,
        "b.py": """\
        from jax.experimental.shard_map import shard_map

        def f(body, mesh, spec):
            return shard_map(body, mesh=mesh, in_specs=spec,
                             out_specs=spec)
        """,
        "c.py": """\
        from deepspeed_tpu.parallel.shard_map_compat import shard_map

        def f(body, mesh, spec):
            return shard_map(body, mesh=mesh, in_specs=spec,
                             out_specs=spec)
        """})
    hits = {f.path for f in fs if f.rule == "MESH004"}
    assert hits == {"a.py", "b.py"}


# ---------------------------------------------------------------------------
# LIFE family — resource lifecycle (PR 7)
# ---------------------------------------------------------------------------
def test_life_alloc_without_free(tmp_path):
    fs = run_lint(tmp_path, {"serving.py": """\
        class Leaky:
            def __init__(self, alloc):
                self.alloc = alloc

            def admit(self, seq, tokens):
                table, cached = self.alloc.allocate(seq, tokens)
                return table

        class Paired:
            def __init__(self, alloc):
                self.alloc = alloc

            def admit(self, seq, tokens):
                return self.alloc.allocate(seq, tokens)

            def finish(self, seq):
                self.alloc.free(seq)

            def preempt(self, seq):
                self.alloc.free(seq, discard=True)
        """})
    hits = [f for f in fs if f.rule == "LIFE001"]
    assert len(hits) == 1 and hits[0].scope == "Leaky.admit"


def test_life_fork_counts_as_alloc(tmp_path):
    fs = run_lint(tmp_path, {"serving.py": """\
        class Forker:
            def __init__(self, allocator):
                self.allocator = allocator

            def split(self, seq, new):
                self.allocator.fork(seq, new)
        """})
    hits = [f for f in fs if f.rule == "LIFE001"]
    assert len(hits) == 1 and hits[0].detail.startswith("fork:")


def test_life_non_allocator_receivers_exempt(tmp_path):
    """allocate() on something that is not allocator-shaped (no 'alloc'
    in the receiver, no *Allocator construction) is out of scope."""
    fs = run_lint(tmp_path, {"m.py": """\
        class Client:
            def __init__(self, arena):
                self.arena = arena

            def get(self):
                return self.arena.allocate(4096)
        """})
    assert [f for f in fs if f.rule == "LIFE001"] == []


def test_life_terminal_status_outside_terminalize(tmp_path):
    fs = run_lint(tmp_path, {"serving.py": """\
        import enum

        class RequestStatus(enum.Enum):
            OK = "ok"
            FAILED = "failed"

        class Scheduler:
            def _terminalize(self, req, status):
                req.status = req.status or status     # the one stamp point

            def quarantine(self, req):
                req.status = RequestStatus.FAILED     # bypasses it

        class Engine:
            def cancel(self, req):
                req.status = RequestStatus.OK         # bypasses it
        """})
    hits = sorted(f.detail for f in fs if f.rule == "LIFE002")
    assert hits == ["FAILED", "OK"]


def test_drift_undocumented_injector_site(tmp_path):
    """DRIFT003 subsumes the old LIFE003 doc-catalog check: a site
    missing from the resilience.md catalog is flagged (no run_tests.sh
    in the fixture tree, so the matrix half stays silent)."""
    fs = run_lint(tmp_path, {
        "docs_stub.py": "",
        "m.py": """\
        from .resilience import get_fault_injector

        def hot_path():
            get_fault_injector().check("serving.allocate")
            get_fault_injector().check("serving.brand_new_site")
        """})
    # write the catalog AFTER run_lint created the tree, then re-lint
    doc = tmp_path / "docs" / "resilience.md"
    doc.parent.mkdir(exist_ok=True)
    doc.write_text("Sites: `serving.allocate`, `other.site`.\n")
    fs = lint_paths([str(tmp_path)], root=str(tmp_path))
    hits = [f for f in fs if f.rule == "DRIFT003"]
    assert len(hits) == 1 and hits[0].detail == "serving.brand_new_site"
    assert "documented catalog" in hits[0].message
    assert not any(f.rule == "LIFE003" for f in fs), "LIFE003 is retired"


def test_drift_no_catalog_doc_stays_silent(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        from .resilience import get_fault_injector

        def hot_path():
            get_fault_injector().check("serving.allocate")
        """})
    assert [f for f in fs if f.rule == "DRIFT003"] == []


def test_repo_injector_sites_all_documented(repo_findings):
    """Every live FaultInjector site appears in docs/resilience.md's
    catalog AND in a run_tests.sh chaos matrix (DRIFT003 green on the
    real tree)."""
    assert [f.render() for f in repo_findings if f.rule == "DRIFT003"] == []


# ---------------------------------------------------------------------------
# engine invariants (PR 7): self-lint, single-parse pin, SARIF
# ---------------------------------------------------------------------------
def test_analyzer_clean_on_own_source():
    """The linter lints itself (tools/lint) with no baseline: an
    analyzer that trips its own rules cannot be trusted to arbitrate
    anyone else's."""
    lint_dir = os.path.join(PKG, "tools", "lint")
    fs = lint_paths([lint_dir], root=REPO_ROOT)
    assert fs == [], "\n".join(f.render() for f in fs)


@pytest.mark.slow
def test_single_parse_matches_per_family_parse():
    """Byte-identical findings from the shared-symbol-table run vs a
    fresh parse per family — pins that the PR 7 single-parse refactor
    changed performance, not semantics."""
    from deepspeed_tpu.tools.lint.core import all_families, load_project
    shared = load_project([PKG], root=REPO_ROOT)
    combined = []
    for _name, run in all_families():
        combined += run(shared)             # one Project, one symtab
    separate = []
    for _name, run in all_families():
        fresh = load_project([PKG], root=REPO_ROOT)   # re-parse per family
        separate += run(fresh)
    key = lambda f: (f.path, f.line, f.col, f.rule)   # noqa: E731
    blob_a = "\n".join(f.render() for f in sorted(combined, key=key))
    blob_b = "\n".join(f.render() for f in sorted(separate, key=key))
    assert blob_a.encode() == blob_b.encode()


def _sarif_of(tmp_path, sources, baseline_findings=0):
    from deepspeed_tpu.tools.lint.cli import RULE_CATALOG
    from deepspeed_tpu.tools.lint.sarif import to_sarif
    fs = run_lint(tmp_path, sources)
    return fs, to_sarif(fs[baseline_findings:], fs[:baseline_findings],
                        RULE_CATALOG)


def test_sarif_validates_against_2_1_0_schema(tmp_path):
    """Structural validation of the invariants the 2.1.0 schema
    requires: version/$schema, runs[].tool.driver.name + rules[].id,
    results[].{ruleId,message.text,locations[].physicalLocation},
    1-based columns, levels from the sarif vocabulary, and suppressions
    on baselined results."""
    fs, log = _sarif_of(tmp_path, {"m.py": """\
        import jax

        @jax.jit
        def step(x):
            return x.item()
        """}, baseline_findings=1)
    assert log["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in log["$schema"]
    assert len(log["runs"]) == 1
    run = log["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "dstpu-lint"
    rule_ids = [r["id"] for r in driver["rules"]]
    assert rule_ids == sorted(rule_ids) and "SYNC001" in rule_ids
    for r in driver["rules"]:
        assert r["shortDescription"]["text"]
    assert run["results"], "findings must emit results"
    for res in run["results"]:
        assert res["ruleId"] in rule_ids
        assert driver["rules"][res["ruleIndex"]]["id"] == res["ruleId"]
        assert res["level"] in ("none", "note", "warning", "error")
        assert res["message"]["text"]
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"] == "m.py"
        assert loc["region"]["startLine"] >= 1
        assert loc["region"]["startColumn"] >= 1
        assert res["partialFingerprints"]["dstpuLintKey/v1"]
    # the baselined finding is suppressed, the live one is not
    suppressed = [r for r in run["results"] if r.get("suppressions")]
    assert len(suppressed) == 1
    assert suppressed[0]["suppressions"][0]["kind"] == "external"


def test_sarif_cli_artifact(tmp_path, capsys):
    """--sarif writes a loadable artifact alongside the normal gate."""
    src = tmp_path / "pkg"
    src.mkdir()
    (src / "m.py").write_text(textwrap.dedent("""\
        import jax

        @jax.jit
        def step(x):
            return x.item()
        """))
    out = tmp_path / "lint.sarif"
    rc = lint_main([str(src), "--root", str(tmp_path), "--no-baseline",
                    "--sarif", str(out)])
    capsys.readouterr()
    assert rc == 1
    log = json.loads(out.read_text())
    assert log["version"] == "2.1.0"
    assert log["runs"][0]["results"]


def test_min_severity_filter(tmp_path):
    """Severity tiers: --min-severity error drops the warning-tier
    findings (step-hot SYNC is warning; jit-hot is error)."""
    sources = {"m.py": """\
        import numpy as np

        def train_step(batch):
            return np.asarray(batch)
        """}
    warn = run_lint(tmp_path, sources)
    assert any(f.severity == "warning" for f in warn)
    errs = lint_paths([str(tmp_path)], root=str(tmp_path),
                      min_severity="error")
    assert errs == []


def test_mesh_axis_kwarg_does_not_mask_positional_name(tmp_path):
    """all_gather's ``axis=`` kwarg is the INTEGER array axis — its
    presence must not suppress checking the positional axis NAME."""
    fs = run_lint(tmp_path, {
        "parallel/topology.py": _TOPO_FIXTURE,
        "m.py": """\
        import jax

        def body(x):
            bad = jax.lax.all_gather(x, "bogus_axis", axis=0)
            good = jax.lax.all_gather(x, "data", axis=0)
            return bad + good
        """})
    hits = [f.detail for f in fs if f.rule == "MESH002"]
    assert hits == ["all_gather:bogus_axis"]


def test_sync_isfinite_whitelist_is_math_only(tmp_path):
    """float(math.isfinite(...)) chains are host-scalar; jnp.isfinite of
    a device value is a device bool and float() of it still flags."""
    fs = run_lint(tmp_path, {"m.py": """\
        import math
        import jax.numpy as jnp

        def train_step(batch):
            loss = run_program(batch)
            ok = math.isfinite(1.0)
            fine = int(ok)
            bad = float(jnp.isfinite(loss))
            return fine + bad
        """})
    s2 = [f.detail for f in fs if f.rule == "SYNC002"]
    assert s2 == ["float:jnp.isfinite(loss)"]


# ---------------------------------------------------------------------------
# DET family — determinism on the token-exact serving surface (v3)
# ---------------------------------------------------------------------------
def test_det_adhoc_randomness_scoped_to_serving(tmp_path):
    """Global-PRNG draws are errors under inference/serving/ and out of
    scope elsewhere (training code seeds its own streams)."""
    src = """\
        import random
        import numpy as np

        def pick(replicas):
            return random.choice(replicas)

        def jitter():
            return np.random.rand()
        """
    fs = run_lint(tmp_path, {"inference/serving/router.py": src,
                             "runtime/warmup.py": src})
    hits = [f for f in fs if f.rule == "DET001"]
    assert len(hits) == 2
    assert {f.path for f in hits} == {"inference/serving/router.py"}
    assert sorted(f.detail for f in hits) == ["np.random.rand",
                                              "random.choice"]


def test_det_prngkey_seed_provenance(tmp_path):
    """PRNGKey from a literal or a caller parameter is replayable;
    anything else mints an unpinned stream."""
    fs = run_lint(tmp_path, {"inference/serving/sampler.py": """\
        import jax

        def submit(seed):
            good = jax.random.PRNGKey(seed)
            base = jax.random.PRNGKey(1234)
            bad = jax.random.PRNGKey(id(object()))
            return good, base, bad
        """})
    hits = [f for f in fs if f.rule == "DET001"]
    assert len(hits) == 1 and hits[0].detail.startswith("PRNGKey:")


def test_det_set_into_order_sensitive_sink(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        def order(xs):
            s = {x for x in xs}
            bad = list(s)                        # DET002: list()
            ok = sorted(s)
            n = len({x for x in xs})
            parts = ",".join({str(x) for x in xs})   # DET002: join
            out = []
            for item in s:                       # DET002: ordered loop
                out.append(item)
            return bad, ok, n, parts, out
        """})
    kinds = sorted(f.detail.split(":")[0] for f in fs
                   if f.rule == "DET002")
    assert kinds == ["for", "join", "list()"]


def test_det_wallclock_beside_injectable_clock(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import time

        def policy(req, now):
            t = time.time()          # DET003: dodges the injected clock
            return t

        def fallback(req, now=None):
            now = now if now is not None else time.time()   # the idiom
            return now

        def no_clock(req):
            return time.time()       # no injectable clock: out of scope
        """})
    hits = [f for f in fs if f.rule == "DET003"]
    assert len(hits) == 1
    assert hits[0].scope == "policy" and hits[0].detail == "time.time:now"


def test_det_dict_view_mutation_in_loop(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        def prune(d):
            for k, v in d.items():
                if v is None:
                    d.pop(k)         # DET004: mutates mid-iteration

        def safe(d):
            for k, v in list(d.items()):
                if v is None:
                    d.pop(k)         # snapshot taken first: fine
        """})
    hits = [f for f in fs if f.rule == "DET004"]
    assert len(hits) == 1
    assert hits[0].scope == "prune" and hits[0].detail == "d.items"
    assert hits[0].severity == "error"


# ---------------------------------------------------------------------------
# FLEET family — replica-lifecycle state machine (v3)
# ---------------------------------------------------------------------------
_FLEET_OWNER = """\
    import enum

    class ReplicaState(enum.Enum):
        STARTING = "starting"
        HEALTHY = "healthy"
        DRAINING = "draining"
        RETIRED = "retired"
        DEAD = "dead"

    _TRANSITIONS = {
        ReplicaState.STARTING: (ReplicaState.HEALTHY, ReplicaState.DEAD),
        ReplicaState.HEALTHY: (ReplicaState.DRAINING, ReplicaState.DEAD),
        ReplicaState.DRAINING: (ReplicaState.RETIRED, ReplicaState.DEAD),
        ReplicaState.RETIRED: (),
        ReplicaState.DEAD: (),
    }

    class Replica:
        def __init__(self):
            self.state = ReplicaState.STARTING   # initial: legal

        def mark_healthy(self):
            if self.state is ReplicaState.STARTING:
                self.state = ReplicaState.HEALTHY

        def resurrect(self):
            self.state = ReplicaState.HEALTHY    # FLEET001: unguarded
    """


def test_fleet_transition_validated_against_table(tmp_path):
    fs = run_lint(tmp_path, {"fleet/replica.py": _FLEET_OWNER})
    hits = [f for f in fs if f.rule == "FLEET001"]
    assert len(hits) == 1 and hits[0].scope == "Replica.resurrect"
    assert hits[0].detail == "HEALTHY:unguarded"
    assert hits[0].severity == "error"


def test_fleet_terminal_stamp_outside_owner(tmp_path):
    fs = run_lint(tmp_path, {
        "fleet/replica.py": _FLEET_OWNER,
        "fleet/router.py": """\
        from .replica import ReplicaState

        def drain(r):
            if r.state is ReplicaState.HEALTHY:
                r.state = ReplicaState.DRAINING   # guarded + non-terminal

        def kill(r):
            if r.state is ReplicaState.HEALTHY:
                r.state = ReplicaState.DEAD       # FLEET002: not the owner
        """})
    hits = [f for f in fs if f.rule == "FLEET002"]
    assert len(hits) == 1
    assert hits[0].path == "fleet/router.py" and hits[0].detail == "DEAD"
    assert [f for f in fs if f.rule == "FLEET001"
            and f.path == "fleet/router.py"] == []


def test_fleet_no_table_stays_silent(tmp_path):
    fs = run_lint(tmp_path, {"m.py": """\
        import enum

        class ReplicaState(enum.Enum):
            UP = "up"

        def f(r):
            r.state = ReplicaState.UP
        """})
    assert [f for f in fs if f.rule.startswith("FLEET")] == []


# ---------------------------------------------------------------------------
# DRIFT family — code <-> docs <-> CI-script reconciliation (v3)
# ---------------------------------------------------------------------------
def test_drift_metrics_vs_docs_both_directions(tmp_path):
    fs = run_lint(tmp_path, {
        "obs.py": """\
        def setup(registry):
            registry.counter("dstpu_documented_total")
            registry.gauge("dstpu_undocumented_depth")
            for name in ("fwd", "backward"):
                registry.gauge(f"dstpu_phase_{name}_ms")
        """,
        "docs/metrics.md": """\
        | metric | meaning |
        |---|---|
        | `dstpu_documented_total` | covered |
        | `dstpu_phase_<phase>_ms` | templated row matches the f-string |
        | `dstpu_ghost_total` | registered nowhere |
        """})
    d1 = [f for f in fs if f.rule == "DRIFT001"]
    assert [f.detail for f in d1] == ["dstpu_undocumented_depth"]
    assert d1[0].path == "obs.py"
    d2 = [f for f in fs if f.rule == "DRIFT002"]
    assert [f.detail for f in d2] == ["dstpu_ghost_total"]
    assert d2[0].path == "docs/metrics.md"


def test_drift_partial_project_does_not_accuse_docs(tmp_path):
    """A project that registers NO metrics cannot prove a docs row has
    no registrar — DRIFT002 must stay silent (self-lint, --rules runs
    over one directory)."""
    fs = run_lint(tmp_path, {
        "util.py": "def f():\n    return 1\n",
        "docs/metrics.md": """\
        | metric | meaning |
        |---|---|
        | `dstpu_elsewhere_total` | registered in a module not linted |
        """})
    assert [f for f in fs if f.rule.startswith("DRIFT")] == []


def test_drift_site_unswept_by_chaos_matrix(tmp_path):
    """A site in the docs catalog but absent from every run_tests.sh
    DSTPU_FAULTS matrix is still drift: CI never sweeps it."""
    fs = run_lint(tmp_path, {
        "m.py": """\
        from .resilience import get_fault_injector

        def a():
            get_fault_injector().check("covered.site")

        def b():
            get_fault_injector().check("unswept.site")
        """,
        "docs/resilience.md":
            "Sites: `covered.site`, `unswept.site`.\n",
        "run_tests.sh": """\
        MATRIX=(
          "covered.site=fail:1:1"
        )
        """})
    hits = [f for f in fs if f.rule == "DRIFT003"]
    assert len(hits) == 1 and hits[0].detail == "unswept.site"
    assert "chaos matrix" in hits[0].message
    assert "documented catalog" not in hits[0].message


def test_drift_config_key_three_way(tmp_path):
    """DRIFT004 ties dataclass fields, *_DEFAULT constants and docs
    config-table rows together — including nested blocks reached from
    the ServingConfig anchor."""
    fs = run_lint(tmp_path, {
        "pkg/inference/config.py": """\
        from dataclasses import dataclass, field
        from . import constants as C

        @dataclass
        class SloBlock:
            objective: float = C.SLO_OBJECTIVE_DEFAULT

        @dataclass
        class ServingConfig:
            enabled: bool = C.SERVING_ENABLED_DEFAULT
            block_size: int = 16
            slo: SloBlock = field(default_factory=SloBlock)
        """,
        "docs/serving.md": """\
        | key | default | meaning |
        |---|---|---|
        | `serving.enabled` | `false` | fully wired: clean |
        | `serving.slo.objective` | `0.9` | nested anchor walk |
        | `serving.ghost_key` | `1` | no dataclass consumes this |
        """})
    details = sorted(f.detail for f in fs if f.rule == "DRIFT004")
    assert details == ["no-constant:serving.block_size",
                       "stale-doc:serving.ghost_key",
                       "undocumented:serving.block_size"]


# ---------------------------------------------------------------------------
# incremental engine (v3): equivalence, cold==warm, --changed, --fix
# ---------------------------------------------------------------------------
ENGINE_TREE = {
    "inference/serving/router.py": """\
        import random

        def pick(replicas):
            return random.choice(replicas)
        """,
    "hot.py": """\
        import jax

        @jax.jit
        def step(x):
            return x.item()
        """,
    "store.py": """\
        import threading

        class Store:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def put(self, x):
                with self._lock:
                    self._items.append(x)

            def reset(self):
                self._items = []
        """,
    "clean.py": "def ok():\n    return 1\n",
}


def _render_all(findings):
    return "\n".join(f.render() for f in findings)


def test_engine_matches_lint_paths(tmp_path):
    """The cached engine is a drop-in for core.lint_paths: identical
    findings byte-for-byte on a multi-family tree."""
    from deepspeed_tpu.tools.lint.engine import lint_paths_cached
    for rel, src in ENGINE_TREE.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    plain = lint_paths([str(tmp_path)], root=str(tmp_path))
    cached = lint_paths_cached(
        [str(tmp_path)], root=str(tmp_path),
        cache_file=str(tmp_path / ".cache.json"))
    assert _render_all(plain) == _render_all(cached)
    assert {f.rule for f in plain} >= {"DET001", "SYNC001", "LOCK001"}


def test_engine_cold_warm_byte_identical_and_incremental(tmp_path):
    """A warm run replays cached modules and matches the cold run
    byte-for-byte; touching ONE module re-analyzes only it (plus
    dependents); a fresh no-cache run agrees with the warm one."""
    from deepspeed_tpu.tools.lint.engine import (EngineStats,
                                                 lint_paths_cached)
    for rel, src in ENGINE_TREE.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    cache = str(tmp_path / ".cache.json")
    args = ([str(tmp_path)],)
    kw = dict(root=str(tmp_path), cache_file=cache)

    cold_stats = EngineStats()
    cold = lint_paths_cached(*args, stats=cold_stats, **kw)
    assert cold_stats.reanalyzed == cold_stats.total_modules > 0

    warm_stats = EngineStats()
    warm = lint_paths_cached(*args, stats=warm_stats, **kw)
    assert warm_stats.reanalyzed == 0 and warm_stats.cache_loaded
    assert _render_all(cold).encode() == _render_all(warm).encode()

    # touch one module: a second hazard appears, others replay cached
    (tmp_path / "store.py").write_text(
        textwrap.dedent(ENGINE_TREE["store.py"]) + textwrap.dedent("""\

        def reset_again(store):
            store._items = []
        """))
    inc_stats = EngineStats()
    inc = lint_paths_cached(*args, stats=inc_stats, **kw)
    assert 1 <= inc_stats.reanalyzed < inc_stats.total_modules
    fresh = lint_paths_cached(*args, root=str(tmp_path), no_cache=True)
    assert _render_all(inc).encode() == _render_all(fresh).encode()


def test_engine_cache_survives_corruption(tmp_path):
    """A torn/garbage cache file degrades to a cold run, never a crash
    or stale findings."""
    from deepspeed_tpu.tools.lint.engine import (EngineStats,
                                                 lint_paths_cached)
    (tmp_path / "m.py").write_text(textwrap.dedent(HAZARD["m.py"]))
    cache = tmp_path / ".cache.json"
    cache.write_text("{ not json")
    stats = EngineStats()
    fs = lint_paths_cached([str(tmp_path)], root=str(tmp_path),
                           cache_file=str(cache), stats=stats)
    assert [f.rule for f in fs] == ["SYNC001"]
    assert not stats.cache_loaded
    assert stats.reanalyzed == stats.total_modules


@pytest.mark.slow
def test_engine_matches_lint_paths_on_repo():
    """Repo-scale equivalence pin: the incremental engine and the
    per-family path agree byte-for-byte on the live tree."""
    from deepspeed_tpu.tools.lint.engine import lint_paths_cached
    plain = lint_paths([PKG], root=REPO_ROOT)
    cached = lint_paths_cached([PKG], root=REPO_ROOT, no_cache=True)
    assert _render_all(plain).encode() == _render_all(cached).encode()


def _git(tmp_path, *argv):
    import subprocess
    return subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *argv],
        cwd=str(tmp_path), capture_output=True, text=True, check=True)


def test_cli_changed_filters_report(tmp_path, capsys):
    """--changed reports only findings in files touched vs HEAD; the
    committed hazard stays out of the report (but the exit code still
    reflects what IS reported)."""
    import shutil
    if shutil.which("git") is None:  # pragma: no cover
        pytest.skip("git unavailable")
    src = tmp_path / "pkg"
    src.mkdir()
    (src / "old.py").write_text(textwrap.dedent(HAZARD["m.py"]))
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (src / "new.py").write_text(textwrap.dedent("""\
        def train_step(b):
            return b.item()
        """))
    rc = lint_main([str(src), "--root", str(tmp_path), "--no-baseline",
                    "--no-cache", "--changed"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "new.py" in out and "old.py" not in out


def test_cli_changed_without_git_reports_all(tmp_path, capsys):
    src = tmp_path / "pkg"
    src.mkdir()
    (src / "m.py").write_text(textwrap.dedent(HAZARD["m.py"]))
    rc = lint_main([str(src), "--root", str(tmp_path), "--no-baseline",
                    "--no-cache", "--changed"])
    out = capsys.readouterr().out
    assert rc == 1 and "m.py" in out


def test_cli_fix_det002_roundtrip(tmp_path, capsys):
    """--fix wraps the flagged set expression in sorted(...) and the
    re-lint comes back clean (exit 0)."""
    src = tmp_path / "pkg"
    src.mkdir()
    (src / "m.py").write_text(textwrap.dedent("""\
        def order(xs):
            s = {x for x in xs}
            return list(s)
        """))
    assert lint_main([str(src), "--root", str(tmp_path), "--no-baseline",
                      "--no-cache"]) == 1
    capsys.readouterr()
    rc = lint_main([str(src), "--root", str(tmp_path), "--no-baseline",
                    "--no-cache", "--fix"])
    out = capsys.readouterr().out
    assert rc == 0 and "fixed" in out
    assert "list(sorted(s))" in (src / "m.py").read_text()


def test_fix_drift001_appends_stub_rows(tmp_path):
    """The DRIFT001 fixer appends TODO stub rows under the marked docs
    table; the re-lint is DRIFT-clean and a human owns the prose."""
    from deepspeed_tpu.tools.lint.fixes import apply_fixes
    fs = run_lint(tmp_path, {
        "obs.py": """\
        def setup(registry):
            registry.counter("dstpu_existing_total")
            registry.gauge("dstpu_new_depth")
        """,
        "docs/metrics.md": """\
        <!-- dstpu-lint: metrics-table -->

        | metric | meaning |
        |---|---|
        | `dstpu_existing_total` | covered |
        """})
    assert [f.detail for f in fs if f.rule == "DRIFT001"] == \
        ["dstpu_new_depth"]
    counts = apply_fixes(str(tmp_path), fs)
    assert counts == {"docs/metrics.md": 1}
    text = (tmp_path / "docs" / "metrics.md").read_text()
    assert "| `dstpu_new_depth` |" in text and "_TODO" in text
    fs2 = lint_paths([str(tmp_path)], root=str(tmp_path))
    assert [f for f in fs2 if f.rule.startswith("DRIFT")] == []


def test_fix_drift001_declines_without_marker(tmp_path):
    from deepspeed_tpu.tools.lint.fixes import apply_fixes
    fs = run_lint(tmp_path, {
        "obs.py": """\
        def setup(registry):
            registry.counter("dstpu_existing_total")
            registry.gauge("dstpu_new_depth")
        """,
        "docs/metrics.md": """\
        No fixer marker anywhere in this file.

        | metric | meaning |
        |---|---|
        | `dstpu_existing_total` | covered |
        """})
    assert any(f.rule == "DRIFT001" for f in fs)
    assert apply_fixes(str(tmp_path), fs) == {}


def test_sarif_catalog_covers_v3_rules():
    """The SARIF rule catalog (and --list-rules) carries the v3 rule
    ids so forge annotations resolve them."""
    from deepspeed_tpu.tools.lint.cli import RULE_CATALOG
    ids = set(RULE_CATALOG)
    assert {"DET001", "DET002", "DET003", "DET004",
            "DRIFT001", "DRIFT002", "DRIFT003", "DRIFT004",
            "FLEET001", "FLEET002"} <= ids
    assert "LIFE003" not in ids
