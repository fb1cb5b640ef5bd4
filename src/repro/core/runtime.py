"""Unified executor runtime: one Program protocol, three generic drivers.

PRs 1-4 fused every algorithm in the zoo (S-DOT/SA-DOT, F-DOT, B-DOT, the
five baselines) into whole-run scans, then hand-wired each capability per
family: ``streaming/resume.py`` carried four near-identical chunk drivers,
``core/sweep.py`` re-implemented case/seed vmapping three times, and B-DOT
plus the baselines could not checkpoint at all. This module extracts the
shared shape of all of those executors into one protocol:

    Program = (build_body, operands, statics, xs, q0, key0, tail, ...)

* ``build_body(operands, **statics) -> body`` is a MODULE-LEVEL builder
  (its identity is the jit cache key) returning the unified scan body
  ``body((carry, key), x) -> ((carry', key'), (err, sends, counts))``.
  Sync families thread the key through untouched and emit zero-shaped
  sends/counts; async families split the key per outer iteration and emit
  their realized per-round send/awake counts. ``carry`` is an arbitrary
  pytree (a (N, d, r) iterate for S-DOT, padded slabs for F-DOT/B-DOT, a
  (q, s, mq_prev) triple for DeEPCA, stacked column estimates for the
  sequential-deflation baselines, an (iterate, Gilbert–Elliott edge
  state, step) triple for the net-fault families) — because the carry is
  opaque to the drivers, new families like ``core/netfaults.py``'s
  edge-mask fault programs get chunked resume and sweeping for free.
* ``operands`` is a flat tuple of device arrays closed over by the body —
  weight matrices, debias tables, data stacks, ground truth.
* ``statics`` is a hashable tuple of (name, value) pairs — the static
  configuration (t_max, trace_err, mode, ...) forwarded to ``build_body``.
* ``xs`` is the host-side scan input: a (T,) consensus schedule, a
  flattened (vector, inner-iteration) index, or a (C, T) per-case stack.

Three drivers execute any Program:

* ``run_monolithic`` — the whole run as ONE jitted scan chunk (the default
  execution mode of ``sdot``/``fdot``/``bdot``/the fused baselines, which
  are now thin shims over it);
* ``run_chunked`` — the scan executed ``chunk_size`` iterations at a time
  over a checkpointed ``RunState`` pytree; kill-at-any-chunk-boundary
  resume is BIT-identical to the uninterrupted run (chunking a
  ``lax.scan`` is exact, the RNG key rides in the state, and the async
  ledger is rebuilt from the checkpointed buffers). Because the driver is
  generic, every registered family — including B-DOT and all five
  baselines — is restartable;
* ``run_sweep`` — the same chunk program vmapped over a case x seed grid
  (case-stacked operands via ``Program.case_axes``, per-seed inits in the
  leading axes of ``q0``). Sweeps accept the same ``manager``/
  ``chunk_size`` as ``run_chunked``, so a killed multi-day sweep resumes
  mid-grid from its checkpointed sweep-RunState, bitwise equal to the
  uninterrupted sweep.

The jitted chunk program is shared by ALL of the above: its cache key is
(build_body, statics, case_axes, seeded, shapes), so a monolithic run and a
chunked run of the same Program reuse one compiled program per distinct
chunk length, and repeated runs across Program instances with equal
configuration recompile nothing.

Every run is a few host spans on the profiler's clock
(``obs.trace_span``): ``runtime.init`` (the RunState), one
``runtime.dispatch`` per chunk (its ``jit_miss`` is 1 when the chunk
program's jit cache grew), ``runtime.sync`` (``int(state.step)``, the
run's one wait for the device) and ``runtime.finalize``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..obs import get_journal, trace_span
from .metrics import CommLedger

__all__ = ["RunState", "Program", "sync_body", "run_monolithic",
           "lower_monolithic", "run_chunked", "run_sweep", "async_ledger"]


def sync_body(inner):
    """Lift a synchronous outer body ``(carry, x) -> (carry', err)`` into
    the unified scan signature: the RNG key threads through untouched and
    the per-step send/count outputs are zero-shaped (so sync and async
    programs share one RunState layout and one chunk driver)."""

    def body(carry_key, x):
        carry, key = carry_key
        carry, err = inner(carry, x)
        return (carry, key), (err, jnp.zeros(()), jnp.zeros(()))

    return body


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RunState:
    """Everything a fused run needs to continue from a chunk boundary.

    Registered pytree: checkpoints through ``checkpoint/manager.py`` with no
    ad-hoc field plucking, and flows through the jitted chunk programs as a
    native container. Sync runs carry zero-size send/count buffers; async
    runs carry the full (T_o, ...) stacked outputs so the realized ledger
    survives a crash. Sweep programs carry leading (case, seed) lane axes
    on every buffer (and on each leaf of ``q``).
    """

    q: Any                    # algorithm carry pytree (iterate, slabs, ...)
    key: jnp.ndarray          # async RNG carry (zeros for sync runs)
    step: jnp.ndarray         # () int32 — outer iterations completed
    errs: jnp.ndarray         # (lanes..., T_o) error-trace buffer
    sends: jnp.ndarray        # async (lanes..., T_o, *tail) per-round sends
    counts: jnp.ndarray       # async (lanes..., T_o, *tail) awake counts

    def tree_flatten(self):
        return ((self.q, self.key, self.step, self.errs, self.sends,
                 self.counts), None)

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


@dataclasses.dataclass
class Program:
    """One algorithm family's run, in the form every driver understands.

    Families construct these via their ``*_program`` registrars
    (``core/sdot.sdot_program``, ``core/fdot.fdot_program``,
    ``core/bdot.bdot_program``, ``core/baselines.baseline_program``, and
    the sweep constructors in ``core/sweep.py``), which reuse the exact
    ``_prepare_*`` / outer-body pairs of the monolithic executors — so a
    Program run under any driver starts from literally the same device
    values and steps through literally the same per-iteration math.
    """

    build_body: Callable      # module-level: (operands, **statics) -> body
    operands: Tuple           # flat tuple of device arrays
    statics: Tuple            # hashable ((name, value), ...) for build_body
    xs: np.ndarray            # (T,) or (C, T) host-side scan inputs
    q0: Any                   # initial carry pytree (lanes leading in sweeps)
    key0: Optional[jnp.ndarray] = None   # async RNG key; None -> sync dummy
    tail: Tuple[int, ...] = ()           # per-step sends/counts shape
    case_axes: Optional[Tuple] = None    # per-operand vmap axes (sweeps)
    n_cases: int = 0          # 0 -> no case axis; else leading C on q0/xs
    n_seeds: int = 0          # 0 -> no seed axis; else next S axis on q0
    finalize: Optional[Callable] = None  # (state, done) -> family result
    restored_step: int = 0    # set by the driver: step actually restored
                              # from the manager (0 = fresh start)

    @property
    def t_outer(self) -> int:
        return int(self.xs.shape[-1])

    @property
    def lane_shape(self) -> Tuple[int, ...]:
        lanes = ()
        if self.n_cases:
            lanes += (self.n_cases,)
        if self.n_seeds:
            lanes += (self.n_seeds,)
        return lanes


# ---------------------------------------------------------------------------
# the ONE jitted chunk program (shared by every family and driver)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("build", "statics", "case_axes",
                                    "seeded"))
def _chunk_program(state, operands, xs_chunk, *, build, statics, case_axes,
                   seeded):
    """Advance ``state`` by one jitted scan over ``xs_chunk``.

    The body is constructed inside the trace from the static
    ``(build, statics)`` pair, so the jit cache is keyed on the family +
    configuration rather than on per-run closures — monolithic, chunked,
    and sweep executions of the same Program share compiled programs.
    Optional vmaps lift the same body over the seed axis (carry/key axis 0,
    operands broadcast) and the case axis (per-operand ``case_axes``, the
    scan inputs stacked per case).
    """
    kw = dict(statics)

    def lane(ops, carry, key, xs):
        body = build(ops, **kw)
        (c, k), outs = jax.lax.scan(body, (carry, key), xs)
        return c, k, outs

    fn = lane
    if seeded:
        fn = jax.vmap(fn, in_axes=(tuple(None for _ in operands), 0, 0,
                                   None))
    if case_axes is not None:
        fn = jax.vmap(fn, in_axes=(case_axes, 0, 0, 0))
    carry, key, (errs, sends, counts) = fn(operands, state.q, state.key,
                                           xs_chunk)
    lanes = errs.ndim - 1
    at_errs = (jnp.int32(0),) * lanes + (state.step,)
    at_tail = at_errs + (jnp.int32(0),) * (state.sends.ndim - lanes - 1)
    return RunState(
        q=carry, key=key,
        step=state.step + xs_chunk.shape[-1],
        errs=jax.lax.dynamic_update_slice(state.errs, errs, at_errs),
        sends=jax.lax.dynamic_update_slice(state.sends, sends, at_tail),
        counts=jax.lax.dynamic_update_slice(state.counts, counts, at_tail))


# ---------------------------------------------------------------------------
# state init / restore / drive
# ---------------------------------------------------------------------------
def _init_state(program: Program) -> RunState:
    lanes = program.lane_shape
    t_outer = program.t_outer
    key = (program.key0 if program.key0 is not None
           else jnp.zeros(lanes, jnp.uint32))
    return RunState(
        q=program.q0,
        key=key,
        step=jnp.int32(0),
        errs=jnp.zeros(lanes + (t_outer,), jnp.float32),
        sends=jnp.zeros(lanes + (t_outer,) + program.tail, jnp.float32),
        counts=jnp.zeros(lanes + (t_outer,) + program.tail, jnp.float32),
    )


def _restore_any(manager: Optional[CheckpointManager], like: RunState):
    """Newest restorable snapshot, skipping corrupt/half-written steps.

    A crashed writer can leave the latest step directory unreadable (the
    manager's atomic rename protects against *partial* publishes, but a
    torn disk or an operator cp can still corrupt shards). Walk the steps
    newest-first; the first one that restores wins; none -> fresh start."""
    if manager is None:
        return None
    steps = manager.all_steps()
    for step in reversed(steps):
        try:
            state, _ = manager.restore(like, step=step)
        except Exception:
            continue
        # restore_tree checks tree structure, not shapes — a snapshot from
        # a run with a different t_outer (or engine size) unflattens fine
        # but its buffers are the wrong length; reject it here so stale
        # directories can't silently produce truncated/overwritten traces
        shapes_ok = all(jax.tree.leaves(jax.tree.map(
            lambda a, b: np.shape(a) == np.shape(b), state, like)))
        if shapes_ok:
            return state
    if steps:
        # every snapshot rejected — distinguish "fresh directory" from a
        # probable operator error (e.g. resuming with a different t_outer
        # or engine shape, which changes the RunState buffer shapes)
        warnings.warn(
            f"{len(steps)} checkpoint step(s) in {manager.root} exist but "
            "none restored against this run's RunState shapes — starting "
            "from iteration 0 (wrong t_outer / engine for this directory?)")
    return None


def _drive_chunks(state: RunState, program: Program, chunk_size: int,
                  manager: Optional[CheckpointManager],
                  max_chunks: Optional[int],
                  target_step: Optional[int] = None) -> RunState:
    """The outer chunk loop: scan a chunk, checkpoint, repeat.

    The completed-step counter is mirrored on the host (read from the
    device exactly once, at restore) so chunk programs enqueue back-to-back
    with NO per-chunk device sync — without checkpointing, a chunked run is
    pure dispatch pipelining over the monolithic scan. Saves are async
    (``blocking=False``) so serialization overlaps the next chunk's
    compute; the manager's atomic rename guarantees a kill mid-save leaves
    the previous step intact. ``max_chunks`` lets tests and benchmarks
    simulate a job killed at a chunk boundary. ``target_step`` stops at an
    ABSOLUTE outer step instead of a relative chunk count — the idempotent
    form an incremental caller wants: if the restored state is already at
    (or past) the target, nothing runs, so re-executing a crashed
    increment can never double-advance the run."""
    t_outer = program.t_outer
    seeded = program.n_seeds > 0
    case_axes = program.case_axes if program.n_cases else None
    step = int(state.step)                   # the one host sync (restore)
    done = 0
    # Out-of-band tracing: journal writes are host-side appends with no
    # device sync, so the dispatch pipelining above is preserved. Per-chunk
    # "dispatch_s" is enqueue time only; a jit-cache-size delta separates
    # compile chunks from steady-state ones, in the journal and as the
    # profiler span's ``jit_miss``.
    j = get_journal()
    step0, t_start = step, time.monotonic()
    while step < t_outer:
        if max_chunks is not None and done >= max_chunks:
            break
        if target_step is not None and step >= target_step:
            break
        length = min(chunk_size, t_outer - step)
        if target_step is not None:
            length = min(length, target_step - step)
        n_compiled, t0 = _chunk_program._cache_size(), time.monotonic()
        with trace_span("runtime.dispatch") as span:
            xs_chunk = jnp.asarray(program.xs[..., step:step + length],
                                   jnp.int32)
            state = _chunk_program(state, program.operands, xs_chunk,
                                   build=program.build_body,
                                   statics=program.statics,
                                   case_axes=case_axes, seeded=seeded)
            compiled = _chunk_program._cache_size() > n_compiled
            span.count(jit_miss=int(compiled))
        step += length
        if j.enabled:
            j.event("chunk", phase="runtime", step=step, length=length,
                    dispatch_s=round(time.monotonic() - t0, 6),
                    compiled=compiled)
        if manager is not None:
            manager.save(step, state, blocking=False)
        done += 1
    if manager is not None:
        manager.wait()
    if j.enabled and step > step0:
        wall = time.monotonic() - t_start    # incl. the final save barrier
        j.event("chunks_done", phase="runtime", steps=step - step0,
                chunks=done, wall_s=round(wall, 6),
                steps_per_s=round((step - step0) / wall, 3) if wall > 0
                else None)
    return state


def _run(program: Program, manager: Optional[CheckpointManager],
         chunk_size: int, max_chunks: Optional[int],
         target_step: Optional[int] = None):
    with trace_span("runtime.init"):
        like = _init_state(program)
        restored = _restore_any(manager, like)
    # the step the run ACTUALLY resumed from (a corrupt/stale newest
    # checkpoint falls back, so this can differ from manager.latest_step())
    program.restored_step = int(restored.step) if restored is not None else 0
    state = restored if restored is not None else like
    state = _drive_chunks(state, program, chunk_size, manager, max_chunks,
                          target_step)
    with trace_span("runtime.sync"):
        done = int(state.step)
    if program.finalize is None:
        return state
    with trace_span("runtime.finalize"):
        return program.finalize(state, done)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------
def run_monolithic(program: Program):
    """The whole run as one jitted scan chunk (the fused default path)."""
    return _run(program, None, max(program.t_outer, 1), None)


def lower_monolithic(program: Program) -> jax.stages.Lowered:
    """The chunk program ``run_monolithic`` dispatches for ``program``,
    lowered but not run: ``.compile()`` times the compile, and the
    compiled text shows which kernels the run contains."""
    return _chunk_program.lower(
        _init_state(program), program.operands,
        jnp.asarray(program.xs, jnp.int32), build=program.build_body,
        statics=program.statics, case_axes=None, seeded=False)


def run_chunked(program: Program, manager: Optional[CheckpointManager],
                chunk_size: int = 10, max_chunks: Optional[int] = None,
                target_step: Optional[int] = None):
    """The run executed ``chunk_size`` iterations at a time with the
    RunState checkpointed through ``manager`` at every chunk boundary.
    Resume from a kill at any boundary is bit-identical to the
    uninterrupted run; ``max_chunks`` simulates the kill. ``target_step``
    stops at an absolute outer step (idempotent incremental execution —
    the serving layer's warm re-solve advances a few chunks per service
    tick this way while the incumbent subspace keeps answering queries)."""
    return _run(program, manager, chunk_size, max_chunks, target_step)


def run_sweep(program: Program, manager: Optional[CheckpointManager] = None,
              chunk_size: Optional[int] = None,
              max_chunks: Optional[int] = None):
    """Execute a case x seed sweep Program (same driver, vmapped body).

    Without ``manager``/``chunk_size`` this is one compiled program and one
    device call — the monolithic sweep. With them, the sweep-RunState
    (lane axes on every buffer) is checkpointed at chunk boundaries so a
    killed sweep worker resumes mid-grid, bitwise equal to the
    uninterrupted sweep."""
    if not (program.n_cases and program.n_seeds):
        raise ValueError("run_sweep needs a Program with case and seed axes"
                         " (use run_monolithic/run_chunked for single runs)")
    size = chunk_size if chunk_size is not None else max(program.t_outer, 1)
    return _run(program, manager, size, max_chunks)


# ---------------------------------------------------------------------------
# ledger reconstruction
# ---------------------------------------------------------------------------
def async_ledger(sched_np, sends, counts, payload_fn, slices) -> CommLedger:
    """Rebuild the realized async ledger from the RunState buffers."""
    ledger = CommLedger()
    sends_np = np.asarray(sends, np.float64)
    counts_np = np.asarray(counts)
    total = float(sends_np.sum())
    ledger.p2p += total
    ledger.matrices += total
    ledger.scalars += payload_fn(sends_np)
    for t in range(len(sched_np)):
        for sl, rounds in slices(int(sched_np[t])):
            ledger.log_awake_rounds(counts_np[t][sl][:rounds])
    return ledger
