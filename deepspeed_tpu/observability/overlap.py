"""Host/device overlap profiler: where does an iteration's wall time go?

One instrument, two engines.  A **serving iteration** (one
``ServingEngine.step()``) is one record — the span ``serving/iteration``
with its number, begin and end — split into five consecutive, exclusive
phases, marked where the work happens in ``_step_impl`` / ``_dispatch``:

  - ``plan`` — deadline sweep, decode capacity, admissions, promotions,
    terminal drain, gauges, ``next_prefill_chunk`` / ``decoding_slots``;
  - ``operands`` — ``_step_operands``: NumPy fills of the mixed
    program's two host arrays (per-slot state with the block tables,
    the chunk vector); no device program is launched in it;
  - ``enqueue`` — ``self._step_fn(*operands)`` until it returns: the two
    host-to-device transfers and the launch;
  - ``device_wait`` — from that return, where the one result array's
    copy to the host is queued behind the program, until the one
    ``np.asarray`` that reads it: the host blocked on the device;
  - ``apply`` — results into the request records, commit hashing,
    finishes, terminal drain, event flush, flight record: everything
    until ``step`` returns.

A second dispatch in one iteration (a chunk remainder) re-enters
``plan`` .. ``apply`` and the times add up, so the five always sum to the
iteration.  The record also carries what the dispatches did:
``dispatches``, ``decode_rows``, ``chunk_rows`` (rows that carried a
token), ``rows_computed`` (rows of the shape of the step that ran,
whatever rode: the chunk lane's only when the plan had a chunk), and what
crossed between host and device: ``host_arrays_in`` (host arrays passed
to the program: 2 a dispatch) and ``host_reads_out`` (device arrays
materialised on the host: 1 a dispatch), counted by ``_dispatch`` from
what it passed and read; and what the sampler was asked for:
``sampled_rows`` (rows at temperature > 0) and ``filtered_rows`` (those
that also set ``top_k > 0`` or ``top_p < 1``), counted from the operands
the program's own predicates read.

A **training step** is recorded one-shot (``observe``) from the
timestamps the step path already takes: enqueue, device wait, and the
rest as ``plan``.

Terminal **requests** go into a second ring (``note_request``) with
their own stamps: submit, admit, first token, finish.

The device's half of the same question is ``SCOPES``: the layer names
the model code declares with ``jax.named_scope`` where the work happens.
A scope is trace-time metadata — the compiled program is the same
program with or without it — and a TPU trace event carries only its HLO
instruction, so the join runs through the program's own compiled text:
``program_scopes()`` reads the optimized HLO of the step programs this
process has loaded and returns ``{scope_key(instruction): (scope,
recomputed)}``.  It is built when asked for and at no other time.

The **set-up path** — everything before the first iteration — has a log
of its own: ``setup_span(name)`` records ``(id, parent, name, begin,
end)`` around the entry points' and the engines' set-up steps
(``setup/init_inference`` .. ``setup/build_train_step``), and one pair
of ``jax.monitoring`` listeners (``listen_for_builds``, registered once
a process by ``ds.enable_compile_cache()``) folds JAX's own compile
events into one record a program: its name, what tracing, lowering and
the backend compile (or the fetch from the persistent cache) took,
whether the cache held it, the set-up span and the serving iteration
it was built under, and whether an engine declared the program its own
(``own_program``).  ``setup_spans()`` / ``builds(t0_s, t1_s)`` read
them back.

Contract (same as every observability hook in this repo):
  - **no new device syncs** in any path;
  - the set-up log is the one exception to "off by default": it is
    ALWAYS on — a server's set-up has ended before anyone could switch
    it on — and pays for that by being bounded (two Python lists of at
    most ``SETUP_LOG_CAP`` records each, the oldest kept: set-up comes
    first), by preallocating nothing, and by never being called from an
    iteration or a training step: a span wraps a set-up step, and the
    listeners run only when JAX traces, lowers or compiles, never on a
    cached call;
  - disabled (default), every other engine call site is ONE attribute
    check (``if ovl.enabled:``) — no allocation, no clock read, no
    annotation;
  - enabled, a phase mark is one ``perf_counter_ns`` read, and every
    phase is also a ``jax.profiler.TraceAnnotation("serving/<phase>")``
    inside a ``TraceAnnotation("serving/iteration", n=<number>)``, so a
    profiler trace shows them on the host line, on the device trace's
    clock;
  - both rings are preallocated at enable; ``iterations(t0_s, t1_s)`` /
    ``requests(t0_s, t1_s)`` read them back on ``time.perf_counter()``'s
    clock (the one ``perf_counter_ns`` shares);
  - export rides the existing flush boundary: gauges + histograms into
    the metrics registry, a per-iteration track into the Chrome trace
    via the tracer's event-source hook.
"""
from __future__ import annotations

import contextlib
import functools
import math
import re
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

#: overlap iteration tracks render as their own Perfetto process group
OVERLAP_TRACK_PID_OFFSET = 2000

#: the five phases of a serving iteration, in the order they run
PHASES = ("plan", "operands", "enqueue", "device_wait", "apply")
PLAN, OPERANDS, ENQUEUE, DEVICE_WAIT, APPLY = range(len(PHASES))
ITERATION_SPAN = "serving/iteration"
PHASE_SPANS = tuple(f"serving/{p}" for p in PHASES)

#: the layers of a step program, as the model code names them with
#: ``jax.named_scope``: model-agnostic, each used wherever that work
#: happens in any configuration.  Scopes nest and the innermost declared
#: one owns an operation.  ``attn_proj``: q/k/v or the latent down/up
#: projections, rotary, the output projection; ``attn_kernel``: the
#: attention call and what feeds it; ``indexer``: a learned sparse
#: selection's projections and its score kernel; ``select``: the top-k
#: over the scores and the table arithmetic that turns positions into
#: pool rows; ``attn_conv``: what a convolutional attention does to q and k
#: between projection and kernel (causal convolutions, the q-k mean, the
#: per-head normalisation, rotary); ``pool_write``: new rows into the
#: paged pool; ``expert_layout``: the layout kernel, gather and combine
#: around the grouped product (``experts``); ``head``: final norm, logits,
#: the finite flag; ``zero_comm``: the casts, gathers and scatters that
#: move state between its partitioned form and the form compute uses;
#: ``ssm_proj``: a state-space layer's four projections and its causal
#: convolution; ``ssm_scan``: its recurrence (the chunk's scan kernel, the
#: decode rows' one-step update); ``gmu``: a gated memory unit's two
#: products and gate; ``state_io``: what moves a slot's per-sequence state
#: out of and into the buffer the step carries; ``kda_proj``: a gated
#: delta-rule layer's projections, convolutions, gates, gated norm and
#: output projection; ``kda_scan``: its recurrence, which names its two
#: lanes one level further in (``SCOPE_LANES``); ``block_unmask``:
#: generation by diffusion over blocks — a drawn token's confidence, the
#: ranking of a block's masked rows by it and the transfer of the best.
SCOPES = ("embed", "norm", "residual", "attn_proj", "attn_kernel",
          "pool_write", "mlp", "router", "expert_layout", "experts",
          "shared_expert", "head", "sample", "loss", "optimizer",
          "zero_comm", "indexer", "select", "attn_conv", "ssm_proj",
          "ssm_scan", "gmu", "state_io", "kda_proj", "kda_scan",
          "block_unmask")
#: an instruction under no declared scope / one whose key two loaded
#: programs map to different scopes
UNNAMED, AMBIGUOUS = "unnamed", "ambiguous"
#: what a scope may name directly inside itself, where the step's two
#: lanes do different work under one scope (``kda_scan/decode``: the rows'
#: one-step update, ``kda_scan/chunk``: the chunk's blocked form), or
#: where two kinds of layer do (``attn_kernel/window``: the paged walks of
#: the layers that attend a window, ``attn_kernel/full``: of those that
#: attend everything, ``models/window_moe.py``); a reader that asks for
#: lanes gets ``scope/lane``, every other the scope
SCOPE_LANES = ("decode", "chunk", "window", "full")


def scoped(name: str):
    """Decorator: the whole function runs under the declared device scope
    ``name`` (``jax.named_scope``, looked up when the function is
    traced)."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of SCOPES")

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            import jax
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap

#: what an iteration's dispatches did (``count_dispatch``)
COUNTERS = ("dispatches", "decode_rows", "chunk_rows", "rows_computed",
            "host_arrays_in", "host_reads_out",
            "sampled_rows", "filtered_rows",
            # counted IN the mixed program by a block that routes experts
            # and reads a latent pool (models/latent_moe.py), carried out
            # on the dispatch's one result array; 0 for other blocks
            "moe_picks", "moe_picks_held", "moe_picks_zero",
            "moe_rows_max_expert", "moe_experts_touched",
            "latent_tokens_read", "latent_pages_read",
            "latent_pages_in_runs",
            # the plain paged kernel's walks (the blocks that return
            # counters and attend through it), summed over the layers:
            # pages that hold attended keys, and those of them in runs of
            # PAGE_RUN consecutive pool blocks, one DMA an operand
            "kv_pages_read", "kv_pages_in_runs",
            # rows x expert layers through an always-on shared expert
            # (models/sandwich_moe.py); 0 for blocks that have none
            "moe_rows_shared",
            # a learned sparse selection (models/sparse_latent_moe.py),
            # summed over the layers: rows x layers that ran the indexer,
            # context tokens it scored, selected tokens attended, rows x
            # layers that took a handed-on selection; 0 for other blocks
            "index_rows", "index_keys_scored", "sparse_tokens_read",
            "sparse_rows_reused",
            # three kinds of state a slot (models/hybrid_ssm.py), counted
            # in the program: context tokens read by the layers that
            # walk the one full layer's pages and by the window layers
            # (x those layers), (row, state-space layer) pairs through
            # the chunk scan and the decode update, chunk rows that
            # stopped before the cross decoder, chunks that started a
            # slot's state from zero; and on the host the window pages
            # handed back; 0 for other blocks
            "kv_tokens_read_full", "kv_tokens_read_window",
            "ssm_chunk_rows", "ssm_decode_rows", "cross_rows_spared",
            "state_slots_started", "window_blocks_freed",
            # a gated delta rule's state a slot beside a latent pool
            # (models/kda_latent_moe.py), counted in the program: (row,
            # layer) pairs through its decode update and its chunk's
            # blocked form; 0 for other blocks
            "kda_decode_rows", "kda_chunk_rows",
            # generation by diffusion over blocks (the engine's block
            # lane), counted on the host: rows of live slots dispatched
            # (block_length a slot a forward), slot forwards that were
            # commits, and tokens those made visible; 0 for other blocks
            "block_rows", "block_commits", "block_tokens",
            # the dispatch in flight (docs/serving.md): dispatches that
            # were enqueued before their predecessor's result was read,
            # and rows whose result was ignored because their request
            # had ended by the time it arrived
            "ahead_dispatches", "void_rows",
            # rows of the expert layers' row buffers that the gather and
            # the weighted scatter-add visited (moe/dropless.py: blocks
            # walked x BLOCK_ROWS, summed over passes and layers); over
            # moe_picks_held, the rows moved for a pick that needed one
            "moe_rows_moved",
            # the plain paged kernel's walks BY KIND of page, where full
            # layers and window layers stand in one stack
            # (models/window_moe.py, which also counts
            # kv_tokens_read_full / _window, moe_rows_shared and
            # window_blocks_freed above): kv_pages_read / _in_runs of the
            # full layers' walks, and of the window layers' from each
            # walk's first attended page; 0 for other blocks
            "kv_pages_read_full", "kv_pages_in_runs_full",
            "kv_pages_read_window", "kv_pages_in_runs_window")
_COUNTER_AT = {name: k for k, name in enumerate(COUNTERS)}

#: one iteration (or training step).  Times are seconds on
#: ``time.perf_counter()``'s clock; ``<phase>_s`` is the phase's summed
#: time.
ITERATION_DTYPE = np.dtype(
    [("n", np.int64), ("kind", "U8"), ("begin_s", np.float64),
     ("end_s", np.float64)]
    + [(f"{p}_s", np.float64) for p in PHASES]
    + [(c, np.int64) for c in COUNTERS])

#: one terminal request: its own stamps.  One it never reached is NaN.
REQUEST_DTYPE = np.dtype(
    [("submit_time", np.float64), ("admit_time", np.float64),
     ("first_token_time", np.float64), ("finish_time", np.float64)])

#: one set-up span: ``parent`` is the ``id`` of the span that was open
#: around it (-1 at the top).  Seconds on ``time.perf_counter()``'s clock.
SETUP_SPAN_DTYPE = np.dtype(
    [("id", np.int64), ("parent", np.int64), ("name", "U48"),
     ("begin_s", np.float64), ("end_s", np.float64)])

#: one program built (traced, lowered, compiled or fetched): ``trace_s``,
#: ``lower_s``, ``compile_s`` are JAX's own durations (``compile_s``
#: holds the fetch on a cache hit); ``cache`` is ``hit`` / ``miss`` /
#: ``off`` (no persistent cache was asked); ``span`` the id of the set-up
#: span open when the build ended and ``iteration`` the number of the
#: serving iteration open then (-1: none); ``own``: an engine declared
#: the program its own (``own_program``).
BUILD_DTYPE = np.dtype(
    [("fun_name", "U64"), ("begin_s", np.float64), ("end_s", np.float64),
     ("trace_s", np.float64), ("lower_s", np.float64),
     ("compile_s", np.float64), ("cache", "U4"), ("span", np.int64),
     ("iteration", np.int64), ("own", np.bool_)])

#: records each of the set-up log's two lists may hold; the oldest stay
SETUP_LOG_CAP = 1024

#: JAX's monitoring events (jax 0.9.0: every ``/jax/core/compile/*``
#: duration carries ``fun_name``; the cache events arrive between a
#: program's lowering and the end of its backend compile)
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_COMPILE = "/jax/core/compile/backend_compile_duration"
_EV_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
             "/jax/compilation_cache/cache_misses": "miss"}
_WRAPPED = re.compile(r"^\w+\((.*)\)$")     # jit(step) -> step

NAN = float("nan")


def _or_nan(stamp: Optional[float]) -> float:
    return NAN if stamp is None else stamp


# -- device scopes: from a program's compiled text to its layer names -----
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?(%[^\s=]+) = (.+?) [\w-]+\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_FUSED = re.compile(r" fusion\(.*?, calls=(%[^\s,]+)")
_COMPUTATION = re.compile(r"(?:ENTRY )?(%[^\s=]+) \(.*\{$")
_JIT_NAME = re.compile(r"\bp?jit\([^()]*\)")
_NAME = re.compile(r"%[^\s,(){}]+")


def scope_key(instruction: str) -> Optional[str]:
    """What a TPU trace's "XLA Ops" event name and a line of optimized
    HLO text share: the instruction's name and its result shape, layouts
    dropped (the two print layouts and operands differently).  None for
    text that is no instruction."""
    m = _INSTRUCTION.match(instruction)
    return None if m is None else _key(m)


def _key(m: "re.Match") -> str:
    return f"{m.group(1)} = {_LAYOUT.sub('', m.group(2))}"


def scope_of(op_name: str, lanes: bool = False) -> Tuple[str, bool]:
    """An ``op_name`` path (``jit(step)/transpose(jvp(head))/dot_general``)
    -> ``(the innermost declared scope or UNNAMED, whether the operation
    is a backward pass's recomputation of its forward)``.  ``jvp(..)``,
    ``transpose(..)`` and the like are looked through; a jitted
    function's own name is not a scope.  With ``lanes``, a scope whose
    next part is one of ``SCOPE_LANES`` is given as ``scope/lane``."""
    parts = re.split(r"[/()]+", _JIT_NAME.sub("", op_name))
    at = next((i for i in reversed(range(len(parts)))
               if parts[i] in SCOPES), None)
    scope = UNNAMED if at is None else parts[at]
    if lanes and at is not None and parts[at + 1:at + 2] and \
            parts[at + 1] in SCOPE_LANES:
        scope = f"{scope}/{parts[at + 1]}"
    return scope, "rematted_computation" in parts


def _operands(rest: str) -> List[str]:
    """The operand names of an instruction, from the text that follows
    its opcode's opening parenthesis (layouts nest parentheses)."""
    depth = 1
    for at, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if not depth:
            return _NAME.findall(rest[:at])
    return []


def _scopes_of_program(text: str, lanes: bool = False
                       ) -> Dict[str, Tuple[str, bool]]:
    """One program's optimized HLO text -> ``{scope_key: (scope,
    recomputed)}`` for every instruction that can be a trace event (those
    inside a fused computation never are).  An instruction the model
    code gave no scope — the layer scan's slice of a stacked weight, a
    copy the compiler made to suit a kernel's layout — is what feeds its
    users: where those are of one declared scope, it takes it."""
    lines = text.splitlines()
    fused = {m.group(1) for m in map(_FUSED.search, lines) if m}
    computation, skip = [], False
    out: Dict[str, Tuple[str, bool]] = {}

    def close():
        feeds: Dict[str, set] = {}
        for key, (scope, remat), operands in reversed(computation):
            users = feeds.get(key.split(" = ", 1)[0], ())
            if scope == UNNAMED and len(users) == 1:
                scope, = users
            if scope != UNNAMED:
                for name in operands:
                    feeds.setdefault(name, set()).add(scope)
            out[key] = (scope, remat)
        computation.clear()

    for ln in lines:
        head = _COMPUTATION.match(ln)
        if head is not None:
            close()
            skip = head.group(1) in fused
            continue
        m = None if skip else _INSTRUCTION.match(ln)
        if m is not None:
            op_name = _OP_NAME.search(ln)
            computation.append((
                _key(m),
                scope_of(op_name.group(1), lanes) if op_name
                else (UNNAMED, False),
                _operands(ln[m.end():])))
    close()
    return out


def scope_table(texts: Iterable[str], lanes: bool = False
                ) -> Dict[str, Tuple[str, bool]]:
    """The programs' tables merged.  A program that declares no scope at
    all is none of the model's (a cast, a seed) and is left out; a key
    that two programs map to different scopes is ``AMBIGUOUS``."""
    table: Dict[str, Tuple[str, bool]] = {}
    for text in texts:
        scopes = _scopes_of_program(text, lanes)
        if all(s == UNNAMED for s, _ in scopes.values()):
            continue
        for key, (scope, remat) in scopes.items():
            seen = table.get(key)
            if seen is None:
                table[key] = (scope, remat)
            elif seen[0] != scope:
                table[key] = (AMBIGUOUS, False)
            else:
                table[key] = (scope, seen[1] and remat)
    return table


class _Ring:
    """A preallocated ring of records of one dtype; ``n`` counts every
    record ever written.  The caller holds the profiler's lock."""

    def __init__(self, capacity: int, dtype: np.dtype):
        self.capacity, self.dtype = capacity, dtype
        self.rows: Optional[np.ndarray] = None     # allocated at enable
        self.n = 0

    def allocate(self) -> None:
        if self.rows is None:
            self.rows = np.zeros(self.capacity, self.dtype)

    def write(self, row: tuple) -> None:
        if self.rows is not None:
            self.rows[self.n % self.capacity] = row
            self.n += 1

    def held(self) -> np.ndarray:
        """The records still held, oldest first (a copy)."""
        if self.rows is None or not self.n:
            return np.zeros(0, self.dtype)
        if self.n <= self.capacity:
            return self.rows[:self.n].copy()
        at = self.n % self.capacity
        return np.concatenate((self.rows[at:], self.rows[:at]))


class OverlapProfiler:
    """Per-iteration phase accounting and request stamps (module
    singleton).

    Serving protocol (``ServingEngine._step_impl`` / ``_dispatch``)::

        if ovl.enabled: ovl.begin()                 # opens ``plan``
        ...                                         # per dispatch:
        if ovl.enabled: ovl.mark(OPERANDS)
        if ovl.enabled: ovl.mark(ENQUEUE)
        if ovl.enabled: ovl.mark(DEVICE_WAIT)
        if ovl.enabled:
            ovl.mark(APPLY)
            ovl.count_dispatch(decode, chunk, computed, n_in, n_out)
        ...                                         # another dispatch:
        if ovl.enabled: ovl.mark(PLAN)
        ...
        if ovl.enabled: ovl.end()

    Training records one-shot (``ovl.observe("train", ...)``) from the
    timestamps the step path already takes.  The engine step loop is
    single-threaded; the lock only guards the rings against a reader.
    """

    def __init__(self, capacity: int = 16384):
        self.enabled = False
        self._its = _Ring(int(capacity), ITERATION_DTYPE)
        self._reqs = _Ring(int(capacity), REQUEST_DTYPE)
        self._lock = threading.Lock()
        self.rank = 0
        self._metrics: Dict[str, tuple] = {}
        #: number of the open serving iteration (the last one, between
        #: iterations)
        self.iteration = -1
        # open-iteration state
        self._open = False
        self._t0_ns = 0
        self._mark_ns = 0
        self._phase = PLAN
        self._acc_ns = [0] * len(PHASES)
        self._counts = [0] * len(COUNTERS)
        self._annotation = None           # jax.profiler.TraceAnnotation
        self._it_span = None
        self._phase_span = None
        # -- the set-up log: always on, bounded (module docstring) ---------
        self._spans: List[tuple] = []     # closed spans (SETUP_SPAN_DTYPE)
        self._span_ids = 0                # ids handed out
        self._span_open: List[int] = []   # open spans' ids, outermost first
        self._builds: List[tuple] = []    # BUILD_DTYPE rows
        self._own: set = set()            # fun_names the engines declared
        #: fun_name -> (begin_s, trace_s): traced, waiting for a lowering
        self._traced: Dict[str, Tuple[float, float]] = {}
        #: [fun_name, begin_s, trace_s, lower_s, cache]: the program
        #: between its lowering and the end of its backend compile
        self._lowered: Optional[list] = None
        self._listening = False
        #: records the two lists refused because they were full
        self.setup_log_dropped = 0

    # -- configuration -----------------------------------------------------
    def configure(self, enabled: bool, capacity: Optional[int] = None,
                  rank: Optional[int] = None) -> None:
        with self._lock:
            if capacity is not None and int(capacity) > 0 \
                    and int(capacity) != self._its.capacity:
                self._its = _Ring(int(capacity), ITERATION_DTYPE)
                self._reqs = _Ring(int(capacity), REQUEST_DTYPE)
            if rank is not None:
                self.rank = int(rank)
            if enabled:
                self._its.allocate()
                self._reqs.allocate()
                if self._annotation is None:
                    from jax.profiler import TraceAnnotation
                    self._annotation = TraceAnnotation
            self.enabled = bool(enabled)
        if not enabled:
            # the engine's own thread configures: an iteration that was
            # open when the profiler went off is dropped
            self._close_spans()
            self._open = False

    def _metrics_for(self, kind: str) -> tuple:
        m = self._metrics.get(kind)
        if m is not None:
            return m
        from . import get_registry
        reg = get_registry()
        # literal registration per engine kind — dstpu-lint's DRIFT001
        # resolver reads these names, keeping code and the docs metric
        # table verifiably in sync
        if kind == "serving":
            m = (reg.gauge("dstpu_serving_host_plan_ms",
                           "host time (plan + operands + apply) in the "
                           "last serving iteration"),
                 reg.gauge("dstpu_serving_device_wait_ms",
                           "host blocked on device in the last serving "
                           "iteration"),
                 reg.histogram("dstpu_serving_host_plan_seconds",
                               "serving per-iteration host time (plan + "
                               "operands + apply)"),
                 reg.histogram("dstpu_serving_device_wait_seconds",
                               "serving per-iteration device wait"))
        else:
            m = (reg.gauge("dstpu_train_host_plan_ms",
                           "host planning time in the last training step"),
                 reg.gauge("dstpu_train_device_wait_ms",
                           "host blocked on device in the last training "
                           "step"),
                 reg.histogram("dstpu_train_host_plan_seconds",
                               "training per-step host planning time"),
                 reg.histogram("dstpu_train_device_wait_seconds",
                               "training per-step device wait"))
        self._metrics[kind] = m
        return m

    # -- the set-up log ------------------------------------------------------
    @contextlib.contextmanager
    def setup_span(self, name: str):
        """One step of the set-up path: a ``(id, parent, name, begin_s,
        end_s)`` record in the set-up log (always) and a ``trace_span``
        of the same name (when the tracer is on).  Never wrap anything
        that runs once an iteration or once a training step."""
        from . import trace_span
        sid, self._span_ids = self._span_ids, self._span_ids + 1
        parent = self._span_open[-1] if self._span_open else -1
        self._span_open.append(sid)
        begin = time.perf_counter()
        try:
            with trace_span(name, "setup"):
                yield
        finally:
            end = time.perf_counter()
            self._span_open.remove(sid)
            self._log(self._spans, (sid, parent, name, begin, end))

    def _log(self, rows: List[tuple], row: tuple) -> None:
        with self._lock:
            if len(rows) < SETUP_LOG_CAP:
                rows.append(row)
            else:
                self.setup_log_dropped += 1

    def own_program(self, fun_name: str) -> None:
        """An engine declares the program it is about to wrap in
        ``jax.jit`` its own: build records under this name read
        ``own``."""
        self._own.add(fun_name)

    def listen_for_builds(self) -> None:
        """Register the ONE pair of ``jax.monitoring`` listeners that
        writes the build records; a second call does nothing.  JAX calls
        them only when it traces, lowers or compiles."""
        if self._listening:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        self._listening = True

    def _on_duration(self, event: str, secs: float, fun_name: str = "",
                     **_) -> None:
        """Fold one program's three durations into one build record.
        The events of one program arrive in order — its trace (after
        those of the functions it calls), its lowering, the cache's
        answer, its backend compile — on the thread that builds it; two
        threads that build at once may read each other's cache state."""
        if event == _EV_TRACE:
            if len(self._traced) >= SETUP_LOG_CAP:     # traced, never lowered
                self._traced.clear()
            self._traced[fun_name] = (time.perf_counter() - secs, secs)
        elif event == _EV_LOWER:
            fun = _WRAPPED.sub(r"\1", fun_name)
            begin, trace_s = self._traced.get(
                fun, (time.perf_counter() - secs, 0.0))
            self._traced.clear()
            self._lowered = [fun, begin, trace_s, secs, "off"]
        elif event == _EV_COMPILE:
            fun, now = _WRAPPED.sub(r"\1", fun_name), time.perf_counter()
            low, self._lowered = self._lowered, None
            if low is None or low[0] != fun:    # lowered before we listened
                low = [fun, now - secs, 0.0, 0.0, "off"]
            _, begin, trace_s, lower_s, cache = low
            self._log(self._builds, (
                fun, begin, now, trace_s, lower_s, secs, cache,
                self._span_open[-1] if self._span_open else -1,
                self.iteration if self._open else -1, fun in self._own))

    def _on_event(self, event: str, **_) -> None:
        cache = _EV_CACHE.get(event)
        if cache is not None and self._lowered is not None:
            self._lowered[4] = cache

    def setup_spans(self) -> np.ndarray:
        """The closed set-up spans (``SETUP_SPAN_DTYPE``), in the order
        they were opened."""
        with self._lock:
            return np.array(sorted(self._spans), SETUP_SPAN_DTYPE)

    def builds(self, t0_s: float = -math.inf, t1_s: float = math.inf
               ) -> np.ndarray:
        """The build records (``BUILD_DTYPE``) whose end lies in
        ``(t0_s, t1_s]`` on ``time.perf_counter()``'s clock, oldest
        first; ``-inf`` reads "since the process began"."""
        with self._lock:
            held = np.array(self._builds, BUILD_DTYPE)
        return held[(held["end_s"] > t0_s) & (held["end_s"] <= t1_s)]

    def clear_setup_log(self) -> None:
        """Forget the spans, the builds and the declared programs (the
        tests' isolation; the listeners stay)."""
        with self._lock:
            self._spans.clear()
            self._builds.clear()
            self.setup_log_dropped = 0
        self._own.clear()
        self._traced.clear()
        self._lowered = None

    # -- serving iteration protocol ----------------------------------------
    def _close_spans(self) -> None:
        for span in (self._phase_span, self._it_span):
            if span is not None:
                span.__exit__(None, None, None)
        self._phase_span = self._it_span = None

    def begin(self) -> None:
        """Open iteration ``self.iteration + 1`` in its ``plan`` phase."""
        self._close_spans()                 # left open if step() raised
        self.iteration += 1
        self._it_span = self._annotation(ITERATION_SPAN, n=self.iteration)
        self._it_span.__enter__()
        self._phase_span = self._annotation(PHASE_SPANS[PLAN])
        self._phase_span.__enter__()
        now = time.perf_counter_ns()
        self._t0_ns = self._mark_ns = now
        self._phase = PLAN
        self._acc_ns = [0] * len(PHASES)
        self._counts = [0] * len(COUNTERS)
        self._open = True

    def mark(self, phase: int) -> None:
        """Close the running phase and open ``phase``: one clock read.
        Marking the phase that already runs is a no-op."""
        if phase == self._phase or not self._open:
            return
        self._phase_span.__exit__(None, None, None)
        now = time.perf_counter_ns()
        self._acc_ns[self._phase] += now - self._mark_ns
        self._mark_ns = now
        self._phase = phase
        self._phase_span = self._annotation(PHASE_SPANS[phase])
        self._phase_span.__enter__()

    def count_dispatch(self, decode_rows: int, chunk_rows: int,
                       rows_computed: int, host_arrays_in: int = 0,
                       host_reads_out: int = 0, sampled_rows: int = 0,
                       filtered_rows: int = 0, **more_counts: int
                       ) -> None:
        """One dispatch of the serving step: the rows that carried a
        token (decoding slots, prompt-chunk tokens), the rows of the
        shape that ran whatever rode, the host arrays passed to it and the
        device arrays read back from it, and the rows that asked the
        sampler for a draw and for a filter (0 sampled: the dispatch
        took the ``argmax``-only side; 0 filtered: it sorted nothing);
        ``more_counts`` — the rest of ``COUNTERS`` by name: what the
        program counted itself (``moe_picks`` .. ``moe_rows_shared``),
        and ``ahead_dispatches`` (1 if this dispatch was enqueued before
        its predecessor's result was read) and ``void_rows`` (rows of it
        whose result was ignored)."""
        for k, add in enumerate((1, decode_rows, chunk_rows, rows_computed,
                                 host_arrays_in, host_reads_out,
                                 sampled_rows, filtered_rows)):
            self._counts[k] += add
        for name, add in more_counts.items():
            self._counts[_COUNTER_AT[name]] += add

    def end(self, kind: str = "serving") -> None:
        if not self._open:
            return
        self._open = False
        now = time.perf_counter_ns()
        self._acc_ns[self._phase] += now - self._mark_ns
        self._close_spans()
        self._record(self.iteration, kind, self._t0_ns * 1e-9, now * 1e-9,
                     [a * 1e-9 for a in self._acc_ns], self._counts)

    def _record(self, n: int, kind: str, begin_s: float, end_s: float,
                phase_s: List[float], counts: List[int]) -> None:
        """Export the host / wait split and write one iteration record
        (``ITERATION_DTYPE``'s field order)."""
        g_plan, g_wait, h_plan, h_wait = self._metrics_for(kind)
        host_s = phase_s[PLAN] + phase_s[OPERANDS] + phase_s[APPLY]
        g_plan.set(host_s * 1e3)
        g_wait.set(phase_s[DEVICE_WAIT] * 1e3)
        h_plan.observe(host_s)
        h_wait.observe(phase_s[DEVICE_WAIT])
        with self._lock:
            self._its.write((n, kind, begin_s, end_s, *phase_s, *counts))

    # -- one-shot (training) ----------------------------------------------
    def observe(self, kind: str, total_s: float, enqueue_s: float,
                wait_s: float, t0_ns: Optional[int] = None,
                dispatches: int = 1) -> None:
        """One step from three durations the caller already has: enqueue,
        device wait, and the rest of ``total_s`` as ``plan``."""
        total_s = max(0.0, total_s)
        enqueue_s = max(0.0, min(enqueue_s, total_s))
        wait_s = max(0.0, min(wait_s, total_s - enqueue_s))
        plan_s = max(0.0, total_s - enqueue_s - wait_s)
        t0_s = (t0_ns if t0_ns is not None
                else time.perf_counter_ns()) * 1e-9
        self._record(self._its.n, kind, t0_s, t0_s + total_s,
                     [plan_s, 0.0, enqueue_s, wait_s, 0.0],
                     [dispatches] + [0] * (len(COUNTERS) - 1))

    # -- terminal requests -------------------------------------------------
    def note_request(self, req) -> None:
        """A request reached its terminal state (``_terminalize``'s one
        path, OK included): keep its stamps."""
        row = (req.submit_time, _or_nan(req.admit_time),
               _or_nan(req.first_token_time), _or_nan(req.finish_time))
        with self._lock:
            self._reqs.write(row)

    # -- one-shot (pipeline bubble probe) ----------------------------------
    def record_bubble(self, frac: float) -> None:
        """Measured pipeline-bubble fraction (the pipeline engine's
        ``measure_bubble_fraction`` probe, `runtime/pipe/engine.py`).
        A gauge, not a histogram: the probe is an explicit profiling
        call, and the interesting value is the latest fit."""
        g = self._metrics.get("bubble")
        if g is None:
            from . import get_registry
            g = get_registry().gauge(
                "dstpu_train_bubble_frac",
                "measured pipeline bubble fraction (two-point slope fit "
                "over the compiled schedule)")
            self._metrics["bubble"] = g
        g.set(max(0.0, min(1.0, float(frac))))

    # -- introspection -----------------------------------------------------
    @property
    def recorded(self) -> int:
        return min(self._its.n, self._its.capacity)

    def _window(self, ring: _Ring, key: str, order_key: str,
                t0_s: float, t1_s: float) -> Tuple[np.ndarray, bool]:
        """The held records with ``key`` in ``(t0_s, t1_s]``, oldest
        first, and whether none that could belong there was overwritten:
        records are written in ``order_key`` order, so the ring is
        complete unless it wrapped and its oldest record is already past
        ``t0_s``."""
        with self._lock:
            held, wrapped = ring.held(), ring.n > ring.capacity
        complete = not (wrapped and held[order_key][0] > t0_s)
        return held[(held[key] > t0_s) & (held[key] <= t1_s)], complete

    def iterations(self, t0_s: float, t1_s: float
                   ) -> Tuple[np.ndarray, bool]:
        """``(records, complete)``: the iteration records
        (``ITERATION_DTYPE``) whose end lies in ``(t0_s, t1_s]`` on
        ``time.perf_counter()``'s clock, oldest first, and whether the
        ring still holds every one of them (False once it has wrapped
        past ``t0_s``)."""
        return self._window(self._its, "end_s", "end_s", t0_s, t1_s)

    def requests(self, t0_s: float, t1_s: float
                 ) -> Tuple[np.ndarray, bool]:
        """``(records, complete)``: the terminal requests
        (``REQUEST_DTYPE``) submitted in ``(t0_s, t1_s]``, in the order
        they ended, and whether the ring still holds every one of them
        (False once it has wrapped past a request that ended after
        ``t0_s``)."""
        return self._window(self._reqs, "submit_time", "finish_time",
                            t0_s, t1_s)

    def last(self) -> Optional[Dict[str, Any]]:
        """The newest iteration record.  ``host_plan_s`` is all the host
        time that is neither enqueue nor device wait: plan + operands +
        apply."""
        with self._lock:
            ring = self._its
            if not ring.n or ring.rows is None:
                return None
            rec = ring.rows[(ring.n - 1) % ring.capacity]
            out = {"kind": str(rec["kind"]), "n": int(rec["n"]),
                   "total_s": float(rec["end_s"] - rec["begin_s"]),
                   "host_plan_s": float(rec["plan_s"] + rec["operands_s"]
                                        + rec["apply_s"])}
            for p in PHASES:
                out[f"{p}_s"] = float(rec[f"{p}_s"])
            for k in COUNTERS:
                out[k] = int(rec[k])
            return out

    def reset(self) -> None:
        with self._lock:
            self._its.n = self._reqs.n = 0
        self.iteration = -1

    # -- device scopes -----------------------------------------------------
    def program_scopes(self, lanes: bool = False
                       ) -> Dict[str, Tuple[str, bool]]:
        """``{scope_key(instruction): (scope, recomputed)}`` over the
        step programs this process has loaded, read from the optimized
        HLO the runtime's loaded executables carry: nothing is traced,
        lowered or compiled for it, and nothing is kept between calls.
        Join it to a trace with ``scope_key(event name)``; a key it lacks
        is ``UNNAMED``.  With ``lanes`` a scope that names its lanes
        (``SCOPE_LANES``) reads ``scope/lane``."""
        import jax.extend
        texts = []
        for exe in jax.extend.backend.get_backend().live_executables():
            try:
                texts += [m.to_string() for m in exe.hlo_modules()]
            except jax.errors.JaxRuntimeError:   # it carries no text
                continue
        return scope_table(texts, lanes)

    # -- export (tracer event source) --------------------------------------
    def chrome_events(self, epoch_ns: int, rank: int
                      ) -> List[Dict[str, Any]]:
        """Per-iteration overlap track: one X slice per iteration plus a
        'C' counter series Perfetto renders as a graph; and the set-up
        log on two threads of the same track: one X slice a set-up span,
        one a program built."""
        pid = OVERLAP_TRACK_PID_OFFSET + rank
        with self._lock:
            recs = self._its.held()
        spans, built = self.setup_spans(), self.builds()
        if not (len(recs) or len(spans) or len(built)):
            return []
        kinds = sorted({str(k) for k in recs["kind"]})
        tids = {k: i + 1 for i, k in enumerate(kinds)}
        setup_tid, builds_tid = len(tids) + 1, len(tids) + 2
        out: List[Dict[str, Any]] = [
            {"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
             "args": {"name": f"overlap profiler rank {rank}"}},
            {"ph": "M", "pid": pid, "tid": 0, "name": "process_sort_index",
             "args": {"sort_index": pid}},
        ]
        threads = [(t, f"{k} iterations") for k, t in tids.items()]
        if len(spans):
            threads.append((setup_tid, "set-up spans"))
        if len(built):
            threads.append((builds_tid, "programs built"))
        for t, name in threads:
            out.append({"ph": "M", "pid": pid, "tid": t,
                        "name": "thread_name", "args": {"name": name}})

        def slice_of(rec, tid, name, args, cat="setup"):
            return {"ph": "X", "pid": pid, "tid": tid, "name": name,
                    "cat": cat,
                    "ts": (rec["begin_s"] * 1e9 - epoch_ns) / 1000.0,
                    "dur": float(rec["end_s"] - rec["begin_s"]) * 1e6,
                    "args": args}

        for rec in spans:
            out.append(slice_of(rec, setup_tid, str(rec["name"]),
                                {"id": int(rec["id"]),
                                 "parent": int(rec["parent"])}))
        for rec in built:
            out.append(slice_of(
                rec, builds_tid, str(rec["fun_name"]),
                {"trace_ms": float(rec["trace_s"]) * 1e3,
                 "lower_ms": float(rec["lower_s"]) * 1e3,
                 "compile_ms": float(rec["compile_s"]) * 1e3,
                 "cache": str(rec["cache"]), "span": int(rec["span"]),
                 "iteration": int(rec["iteration"]),
                 "own": bool(rec["own"])}))
        for rec in recs:
            kind = str(rec["kind"])
            phases_ms = {f"{p}_ms": float(rec[f"{p}_s"]) * 1e3
                         for p in PHASES}
            host_ms = (phases_ms["plan_ms"] + phases_ms["operands_ms"]
                       + phases_ms["apply_ms"])
            it = slice_of(rec, tids[kind], f"{kind}_iteration",
                          dict(phases_ms, n=int(rec["n"]),
                               host_plan_ms=host_ms,
                               **{c: int(rec[c]) for c in COUNTERS}),
                          cat="overlap")
            out.append(it)
            out.append({"ph": "C", "pid": pid, "tid": tids[kind],
                        "name": f"{kind}_overlap", "ts": it["ts"],
                        "args": {"host_plan_ms": host_ms,
                                 "device_wait_ms":
                                     phases_ms["device_wait_ms"]}})
        return out


_profiler = OverlapProfiler()


def get_overlap_profiler() -> OverlapProfiler:
    return _profiler
