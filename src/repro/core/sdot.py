"""S-DOT and SA-DOT — sample-wise distributed orthogonal iteration (Alg. 1).

The two algorithms share one implementation; they differ only in the
per-outer-iteration consensus budget ``schedule`` (constant for S-DOT,
increasing for SA-DOT — see ``consensus_schedule``).

Engines:
  * ``sdot`` — simulation over an explicit graph (DenseConsensus). All N node
    states are carried as a stacked (N, d, r) array; this is what reproduces
    the paper's tables.
  * ``sdot_spmd_step`` — the building block used when node == TPU pod; exact
    psum intra-pod, gossip inter-pod (see optim/psa_compress.py).

Execution modes (``fused`` flag):
  * fused (default) — the ENTIRE run is one jitted ``lax.scan`` over outer
    iterations: per-iteration consensus budgets are read from the schedule
    array, the inner gossip is a masked scan (so varying T_{c,t} stays
    traceable), debiasing indexes a precomputed device table of W^t e_1
    rows, and the error trace is computed on device and returned as one
    (T_o,) array. Zero host syncs per iteration, one compile per
    (shapes, t_max) signature, communication accounted in closed form.
    With an ``AsyncConsensus`` engine the whole straggler run is ALSO one
    scan: the RNG key rides in the scan carry, each outer iteration draws
    its (t_max, N) awake-mask block and runs masked realized-matrix gossip
    (exact realized debias), and the per-round send/awake counts come back
    as stacked scan outputs — one dispatch for a whole Table-V run.
  * eager (``fused=False``) — the original Python loop, one dispatch chain
    per outer iteration. Kept as the bit-level correctness oracle
    (tests/test_sdot_fused.py) and for step-by-step debugging. With an
    async engine the eager loop draws the same padded (t_max, N) mask
    blocks, so seeded eager runs replay the fused executor round for round.

``sdot_spmd`` is the node == TPU-pod twin of the fused executor: the same
whole-run scan runs *inside* shard_map over a mesh axis (masked
ppermute/all_gather gossip + the device debias table), so a multi-pod run is
one compiled SPMD program instead of one collective dispatch per iteration.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import runtime
from .async_gossip import masked_async_rounds
from .consensus import (DenseConsensus, consensus_schedule, debias_by_row,
                        debias_table, masked_gossip)
from .netfaults import (masked_faulty_rounds, realized_debias,
                        sample_fault_blocks)
from .linalg import PRECISION, cholesky_qr2, orthonormal_init
from .metrics import CommLedger, mean_subspace_error, subspace_error
from ..kernels import ops as kops
from ..obs import trace_span

__all__ = ["SDOTResult", "sdot", "sadot", "sdot_program", "sdot_spmd",
           "local_cov_apply"]


@dataclasses.dataclass
class SDOTResult:
    q_nodes: jnp.ndarray            # (N, d, r) final per-node estimates
    error_trace: Optional[np.ndarray]   # (T_o,) mean subspace error vs q_true
    consensus_trace: np.ndarray     # (T_o,) consensus rounds used per outer iter
    ledger: CommLedger              # communication accounting

    @property
    def q_mean(self) -> jnp.ndarray:
        """Consensus-averaged estimate (for reporting; nodes already agree)."""
        return self.q_nodes.mean(axis=0)


def local_cov_apply(covs: jnp.ndarray, q_nodes: jnp.ndarray) -> jnp.ndarray:
    """Step 5 of Alg. 1 at every node: Z_i = M_i Q_i. covs: (N,d,d)."""
    return jnp.einsum("nde,ner->ndr", covs, q_nodes, precision=PRECISION)


def _stack_data(xs: Sequence[jnp.ndarray], r: int):
    """Zero-pad ragged node blocks (d, n_i) to one (N, d, width) stack.

    Where the batched gram apply runs its Pallas kernel for (d, r), the
    width is the one the kernel tiles exactly (``kops.gram_tiling(n_max)``),
    so the apply in every outer iteration pads nothing; elsewhere it is
    n_max. Padding is exact for the gram apply (padded columns are null in
    both matmuls); the true n_i go along for the normalizer. Returns
    ``(stack, n_true, tiled)``, ``tiled`` whether the stack has the
    kernel's width.
    """
    n_true = np.array([x.shape[1] for x in xs], np.float32)
    n_max = int(n_true.max())
    width, block_n = kops.gram_tiling(n_max)
    tiled = kops.batched_gram_apply_path(xs[0].shape[0], r,
                                         block_n) == "pallas"
    if not tiled:
        width = n_max
    return _pad_stack(list(xs), width), jnp.asarray(n_true), tiled


@functools.partial(jax.jit, static_argnames="width")
def _pad_stack(xs: list, width: int) -> jnp.ndarray:
    """Blocks (d, n_i) zero-padded to (N, d, width) in one program, which
    writes the stack alone: eager pads and a stack hold two more copies
    of it on the device, and those set the solve's peak memory."""
    return jnp.stack([jnp.pad(x, ((0, 0), (0, width - x.shape[1])))
                      for x in xs])


def _apply_operand(operand, mode: str, q_nodes):
    """Step 5 of Alg. 1 for either operand layout (cov stack or raw data).

    The data mode is gram-free — Z_i = X_i (X_i^T Q_i), never forming the
    (d x d) M_i — and serves all nodes with ONE batched gram-apply dispatch
    (Pallas (node, column-block) kernel on TPU, fused einsum elsewhere)
    instead of a per-node Python loop; both the fused scan body and the
    eager loop call through here.
    """
    if mode == "cov":
        return local_cov_apply(operand, q_nodes)
    x_stack, n_true = operand
    return kops.batched_gram_apply(x_stack, q_nodes, n_true)


def debiased_gossip(w, table, z_stack, t_c, t_max: int):
    """``consensus.debiased_gossip`` with its two halves under S-DOT's
    step scopes, ``sdot.gossip`` and ``sdot.debias``."""
    with jax.named_scope("sdot.gossip"):
        z = masked_gossip(w, z_stack, t_c, t_max)
    with jax.named_scope("sdot.debias"):
        return debias_by_row(table, z, t_c)


def _sync_outer_body(operand, w, table, q_true, node_mask, *, mode: str,
                     t_max: int, trace_err: bool):
    """Build the per-outer-iteration body ``(q_nodes, t_c) -> (q_new, err)``.

    ONE definition feeds every runtime driver (monolithic, chunked, sweep —
    via ``_sdot_build_body``), so a run split at arbitrary chunk boundaries
    replays the monolithic scan bit for bit — the math cannot drift between
    the callers.

    Each step of Alg. 1 runs under a ``jax.named_scope`` (``sdot.apply``,
    ``sdot.gossip``, ``sdot.debias``, ``sdot.qr``, ``sdot.error``), the same
    names in every outer body, so a profiler trace attributes each device
    op to its step; scopes change op metadata only, not the arithmetic.
    """

    def outer(q_nodes, t_c):
        with jax.named_scope("sdot.apply"):
            z0 = _apply_operand(operand, mode, q_nodes)          # (N, d, r)
        v = debiased_gossip(w, table, z0, t_c, t_max)
        with jax.named_scope("sdot.qr"):
            q_new = jax.vmap(lambda vv: cholesky_qr2(vv)[0])(v)  # per-node QR
        with jax.named_scope("sdot.error"):
            err = (mean_subspace_error(q_true, q_new, node_mask)
                   if trace_err else jnp.float32(0.0))
        return q_new, err

    return outer


def _async_outer_body(operand, w, adj, p_awake, q_true, *, mode: str,
                      t_max: int, trace_err: bool):
    """Async twin of ``_sync_outer_body``: carry is ``(q_nodes, rng key)``.

    Each call splits the key, draws the iteration's (t_max, N) awake-mask
    block, and runs realized-matrix gossip — the key ride in the carry is
    exactly what makes chunked resume exact for straggler runs: checkpointing
    the carried key restores the stream mid-run with no replay. Scopes as
    in ``_sync_outer_body``; the realized debias is part of the realized
    rounds, so it runs under ``sdot.gossip``.
    """
    n = w.shape[0]

    def outer(carry, t_c):
        q_nodes, key = carry
        with jax.named_scope("sdot.gossip"):
            key, sub = jax.random.split(key)
            awake = jax.random.bernoulli(sub, p_awake, (t_max, n))
        with jax.named_scope("sdot.apply"):
            z0 = _apply_operand(operand, mode, q_nodes)          # (N, d, r)
        with jax.named_scope("sdot.gossip"):
            v, sends, counts = masked_async_rounds(w, adj, awake, t_c, z0)
        with jax.named_scope("sdot.qr"):
            q_new = jax.vmap(lambda vv: cholesky_qr2(vv)[0])(v)
        with jax.named_scope("sdot.error"):
            err = (mean_subspace_error(q_true, q_new) if trace_err
                   else jnp.float32(0.0))
        return (q_new, key), (err, sends, counts)

    return outer


def _faulty_outer_body(operand, w, adj, params, node_up_sched, table,
                       q_true, *, mode: str, t_max: int, trace_err: bool,
                       debias: str):
    """Network-fault twin of ``_async_outer_body``: the carry is
    ``((q_nodes, ge, t), key)``.

    Each outer iteration splits the key, pre-samples its (t_max, N, N) /
    (t_max, N) fault blocks (the edge-mask twin of the awake-mask draw),
    reads the iteration's crash mask from the (T, N) ``node_up_sched``
    operand via the carried iteration counter ``t``, and runs realized
    edge-mask gossip. The Gilbert–Elliott state ``ge`` and the counter ride
    in the carry, so chunked resume replays bursts and crash windows
    exactly. Crashed nodes contribute no edges and their iterate is FROZEN
    (the QR update is masked), so on rejoin they re-sync from neighbors
    through ordinary gossip. ``debias``: "realized" divides by the carried
    realized mixing product (self-healing); "nominal" divides by the
    fault-free W^t e_1 table row (the uncorrected benchmark arm). Scopes
    as in ``_sync_outer_body``.
    """
    n = w.shape[0]

    def outer(carry, t_c):
        (q_nodes, ge, t), key = carry
        with jax.named_scope("sdot.gossip"):
            key, sub = jax.random.split(key)
            blocks = sample_fault_blocks(sub, n, t_max)
            node_up = jnp.take(node_up_sched, t, axis=0)         # (N,)
        with jax.named_scope("sdot.apply"):
            z0 = _apply_operand(operand, mode, q_nodes)          # (N, d, r)
        with jax.named_scope("sdot.gossip"):
            z, p, ge_new, sends, counts = masked_faulty_rounds(
                w, adj, params, node_up, ge, blocks, t_c, z0)
        with jax.named_scope("sdot.debias"):
            if debias == "realized":
                v = realized_debias(z, p)
            else:
                row = jnp.take(table, t_c, axis=0)
                v = z / row.astype(z.dtype).reshape(
                    (-1,) + (1,) * (z.ndim - 1))
        with jax.named_scope("sdot.qr"):
            q_qr = jax.vmap(lambda vv: cholesky_qr2(vv)[0])(v)
            up = node_up.reshape((-1,) + (1,) * (q_nodes.ndim - 1)) > 0
            q_new = jnp.where(up, q_qr, q_nodes)                 # freeze
        with jax.named_scope("sdot.error"):
            err = (mean_subspace_error(q_true, q_new) if trace_err
                   else jnp.float32(0.0))
        return ((q_new, ge_new, t + 1), key), (err, sends, counts)

    return outer


def _sdot_build_body(operands, *, mode: str, t_max: int, trace_err: bool,
                     is_async: bool, is_faulty: bool = False,
                     debias: str = "realized"):
    """Runtime body builder for S-DOT/SA-DOT (the Program protocol's
    ``build_body``) — a thin adapter over the SAME outer-iteration bodies
    the executors have always used, so every driver (monolithic, chunked,
    sweep) steps through identical per-iteration math."""
    if mode == "cov":
        op, rest = operands[0], operands[1:]
    else:
        op, rest = (operands[0], operands[1]), operands[2:]
    if is_faulty:
        w, adj, params, node_up_sched, table, q_true = rest
        return _faulty_outer_body(op, w, adj, params, node_up_sched, table,
                                  q_true, mode=mode, t_max=t_max,
                                  trace_err=trace_err, debias=debias)
    if is_async:
        w, adj, p_awake, q_true = rest
        return _async_outer_body(op, w, adj, p_awake, q_true, mode=mode,
                                 t_max=t_max, trace_err=trace_err)
    w, table, q_true, node_mask = rest
    return runtime.sync_body(
        _sync_outer_body(op, w, table, q_true, node_mask, mode=mode,
                         t_max=t_max, trace_err=trace_err))


def sdot_program(
    *,
    covs=None,
    data: Optional[Sequence[jnp.ndarray]] = None,
    engine,
    r: int,
    t_outer: int,
    schedule: Optional[np.ndarray] = None,
    t_c: int = 50,
    q_init: Optional[jnp.ndarray] = None,
    q_true: Optional[jnp.ndarray] = None,
    seed: int = 0,
) -> runtime.Program:
    """Register an S-DOT/SA-DOT run with the unified executor runtime.

    Built from the same ``_prepare_sdot`` pieces as the eager oracle, so a
    Program run under any driver starts from literally the same device
    values. ``runtime.run_monolithic`` reproduces ``sdot(fused=True)``;
    ``runtime.run_chunked`` is the restartable twin (streaming/resume.py).

    With ``data`` the ``sdot.prepare`` span counts the sample columns of
    the stack built (``stack_cols``) and whether that is the gram kernel's
    tiled width, so the apply pads nothing (``stack_tiled``, 1 or 0).
    """
    with trace_span("sdot.prepare") as span:
        prep = _prepare_sdot(covs=covs, data=data, engine=engine, r=r,
                             t_outer=t_outer, schedule=schedule, t_c=t_c,
                             q_init=q_init, q_true=q_true, seed=seed)
        if prep["mode"] == "data":
            span.count(stack_cols=prep["operand"][0].shape[2],
                       stack_tiled=int(prep["stack_tiled"]))
    n, d = prep["n"], prep["d"]
    t_max, trace_err, q_arg = prep["t_max"], prep["trace_err"], prep["q_arg"]
    sched_np = prep["sched_np"]
    is_async = prep["is_async"]
    is_faulty = prep["is_faulty"]
    mode = prep["mode"]
    debias = engine.debias if is_faulty else "realized"
    q0 = prep["q_nodes"]
    op_flat = ((prep["operand"],) if mode == "cov" else
               tuple(prep["operand"]))
    if is_faulty:
        node_up_sched = jnp.asarray(
            engine.faults.validate(n, t_outer).node_up(t_outer, n))
        operands = op_flat + (engine._w, engine._adj, engine._params,
                              node_up_sched, debias_table(engine._w, t_max),
                              q_arg)
        key0, tail = engine._key, (t_max,)
        q0 = (q0, engine._ge, jnp.int32(0))
    elif is_async:
        operands = op_flat + (engine._w, engine._adj,
                              jnp.asarray(engine.p_awake, jnp.float32),
                              q_arg)
        key0, tail = engine._key, (t_max,)
    else:
        if not hasattr(engine, "debias_table"):
            raise ValueError("fused S-DOT needs a fused-capable engine "
                             "(debias_table) or an async engine")
        operands = op_flat + (engine._w, engine.debias_table(t_max), q_arg,
                              jnp.ones((n,), jnp.float32))
        key0, tail = None, ()
    payload = d * r

    def finalize(state: runtime.RunState, done: int) -> SDOTResult:
        q_nodes = state.q[0] if is_faulty else state.q
        if is_async or is_faulty:
            if done == t_outer:
                engine._key = state.key   # same stream position as eager
                if is_faulty:
                    engine._ge = state.q[1]   # burst state carries over too
            ledger = runtime.async_ledger(
                sched_np[:done], state.sends[:done], state.counts[:done],
                lambda s: float(s.sum()) * payload,
                lambda t_c_t: [(slice(None), t_c_t)])
        else:
            ledger = CommLedger()
            ledger.log_gossip_rounds(sched_np[:done],
                                     engine.graph.adjacency, payload,
                                     bytes_per_elem=getattr(
                                         engine, "payload_bytes_per_elem",
                                         4.0))
        return SDOTResult(
            q_nodes=q_nodes,
            error_trace=(np.asarray(state.errs[:done]) if trace_err
                         else None),
            consensus_trace=sched_np[:done],
            ledger=ledger,
        )

    return runtime.Program(
        build_body=_sdot_build_body,
        operands=operands,
        statics=(("mode", mode), ("t_max", t_max), ("trace_err", trace_err),
                 ("is_async", is_async), ("is_faulty", is_faulty),
                 ("debias", debias)),
        xs=sched_np,
        q0=q0,
        key0=key0,
        tail=tail,
        finalize=finalize,
    )


def _prepare_sdot(*, covs, data, engine, r, t_outer, schedule, t_c, q_init,
                  q_true, seed):
    """Validate + normalize a run's inputs into device-ready pieces.

    Shared by ``sdot`` and the chunked streaming executor
    (``streaming/resume.py``): both construct the operand stack, schedule
    array, debias-table bounds, and initial iterate through this one helper,
    so a chunked run starts from literally the same device values as the
    monolithic one. Returns a dict of run pieces.
    """
    if (covs is None) == (data is None):
        raise ValueError("provide exactly one of covs / data")
    n = engine.graph.n_nodes
    if covs is not None:
        d = covs.shape[1]
        if covs.shape[0] != n:
            raise ValueError("covs leading dim must equal number of nodes")
    else:
        d = data[0].shape[0]
        if len(data) != n:
            raise ValueError("need one data block per node")

    if schedule is None:
        schedule = consensus_schedule("const", t_outer, t_max=t_c)
    elif len(schedule) < t_outer:
        # fail loudly: the fused scan would silently truncate the run and
        # the eager loop would IndexError mid-flight
        raise ValueError(f"schedule has {len(schedule)} entries but "
                         f"t_outer={t_outer}")
    if q_init is None:
        q_init = orthonormal_init(jax.random.PRNGKey(seed), d, r)
    # all nodes start from the same Q_init (Theorem 1 requires it)
    q_nodes = jnp.broadcast_to(q_init[None], (n, d, r))

    is_faulty = hasattr(engine, "sample_faults")
    is_async = (not is_faulty) and hasattr(engine, "sample_awake")
    sched_np = np.asarray(schedule[:t_outer])
    t_max = int(sched_np.max()) if t_outer else 0
    trace_err = q_true is not None
    q_arg = q_true if trace_err else jnp.zeros((d, r), q_nodes.dtype)
    stack_tiled = False
    if covs is not None:
        operand, mode = covs, "cov"
    else:
        x_stack, n_true, stack_tiled = _stack_data(data, r)
        operand, mode = (x_stack, n_true), "data"
    return dict(
        n=n, d=d, operand=operand, mode=mode, stack_tiled=stack_tiled,
        q_nodes=q_nodes,
        schedule=schedule, sched_np=sched_np,
        sched_dev=jnp.asarray(sched_np, jnp.int32), t_max=t_max,
        trace_err=trace_err, q_arg=q_arg, is_async=is_async,
        is_faulty=is_faulty,
    )


def sdot(
    *,
    covs: Optional[jnp.ndarray] = None,
    data: Optional[Sequence[jnp.ndarray]] = None,
    engine: DenseConsensus,
    r: int,
    t_outer: int,
    schedule: Optional[np.ndarray] = None,
    t_c: int = 50,
    q_init: Optional[jnp.ndarray] = None,
    q_true: Optional[jnp.ndarray] = None,
    seed: int = 0,
    fused: bool = True,
) -> SDOTResult:
    """Run S-DOT / SA-DOT over a simulated network.

    Exactly one of ``covs`` (N, d, d) or ``data`` (list of (d, n_i)) must be
    given. ``schedule`` overrides ``t_c`` (constant) and makes this SA-DOT.
    ``fused=True`` (default) executes the whole run as a single compiled
    scan (a thin shim over ``runtime.run_monolithic``); ``fused=False`` is
    the eager per-iteration oracle.

    The fused run is one ``sdot.solve`` host span on the profiler's clock
    (``obs.trace_span``) whose counts are the gossip rounds the masked scan
    runs (``rounds_run``, t_outer * t_max) and those the schedule asks for
    (``rounds_needed``), and on a sparse engine those the Pallas ELL
    kernel runs (``ell_pallas_rounds``: all of them where
    ``engine.gossip_path`` at the payload width the solve mixes, d r, is
    'pallas', else 0); building the Program is ``sdot.program`` (with
    ``sdot.prepare`` in it), and the runtime adds its own spans.
    """
    # async / faulty engines get their own whole-run scan (the RNG key —
    # and for faults the Gilbert–Elliott state — rides in the carry); any
    # other engine without the scan interface runs eagerly
    if fused and (hasattr(engine, "sample_awake")
                  or hasattr(engine, "sample_faults")
                  or hasattr(engine, "debias_table")):
        with trace_span("sdot.solve") as span:
            with trace_span("sdot.program"):
                program = sdot_program(
                    covs=covs, data=data, engine=engine, r=r,
                    t_outer=t_outer, schedule=schedule, t_c=t_c,
                    q_init=q_init, q_true=q_true, seed=seed)
            rounds_run = program.t_outer * dict(program.statics)["t_max"]
            counts = dict(rounds_run=rounds_run,
                          rounds_needed=int(program.xs.sum()))
            if getattr(engine, "is_sparse", False):
                d = covs.shape[1] if covs is not None else data[0].shape[0]
                counts["ell_pallas_rounds"] = (
                    rounds_run if engine.gossip_path(d * r) == "pallas"
                    else 0)
            span.count(**counts)
            return runtime.run_monolithic(program)

    prep = _prepare_sdot(covs=covs, data=data, engine=engine, r=r,
                         t_outer=t_outer, schedule=schedule, t_c=t_c,
                         q_init=q_init, q_true=q_true, seed=seed)
    operand, mode = prep["operand"], prep["mode"]
    q_nodes, schedule = prep["q_nodes"], prep["schedule"]
    t_max = prep["t_max"]
    is_async = prep["is_async"]
    is_faulty = prep["is_faulty"]
    if is_faulty:
        n = engine.graph.n_nodes
        node_up_sched = engine.faults.validate(n, t_outer).node_up(
            t_outer, n)

    ledger = CommLedger()
    errs = [] if q_true is not None else None
    for t in range(t_outer):
        z0 = _apply_operand(operand, mode, q_nodes)               # (N, d, r)
        if is_faulty:
            # draw with the fused executor's padded shape so a seeded
            # eager run replays the fused scan fault for fault
            blocks = engine.sample_faults(int(schedule[t]), t_max=t_max)
            node_up = node_up_sched[t]
            v = engine.run_debiased(z0, int(schedule[t]), ledger,
                                    faults=blocks, node_up=node_up)
            q_qr = jax.vmap(lambda vv: cholesky_qr2(vv)[0])(v)
            up = node_up.reshape((-1,) + (1,) * (q_nodes.ndim - 1)) > 0
            q_nodes = jnp.where(up, q_qr, q_nodes)   # crashed nodes freeze
            if errs is not None:
                e = jax.vmap(lambda qq: subspace_error(q_true, qq))(q_nodes)
                errs.append(float(e.mean()))
            continue
        if is_async:
            # draw with the fused executor's padded shape so a seeded
            # eager run replays the fused scan round for round
            awake = engine.sample_awake(int(schedule[t]), t_max=t_max)
            v = engine.run_debiased(z0, int(schedule[t]), ledger,
                                    awake=awake)
        else:
            v = engine.run_debiased(z0, int(schedule[t]), ledger)
        q_nodes = jax.vmap(lambda vv: cholesky_qr2(vv)[0])(v)
        if errs is not None:
            e = jax.vmap(lambda qq: subspace_error(q_true, qq))(q_nodes)
            errs.append(float(e.mean()))
    error_trace = np.asarray(errs) if errs is not None else None

    return SDOTResult(
        q_nodes=q_nodes,
        error_trace=error_trace,
        consensus_trace=np.asarray(schedule[:t_outer]),
        ledger=ledger,
    )


def sadot(*, schedule_kind: str = "lin2", cap: Optional[int] = None,
          t_outer: int, **kw) -> SDOTResult:
    """SA-DOT convenience wrapper: increasing consensus schedule."""
    sched = consensus_schedule(schedule_kind, t_outer, cap=cap)
    return sdot(t_outer=t_outer, schedule=sched, **kw)


def _spmd_program(engine, t_max: int, trace_err: bool):
    """The jitted whole-run shard_map program of ``sdot_spmd`` for
    ``engine``, built on first use and kept on the engine, keyed by what
    it bakes in beside the engine (its mesh, axis, ring coefficients or
    weight row): the static inner scan length ``t_max`` and whether the
    pmean'd error is traced. Covariances, iterates, schedule, debias table
    and ``q_true`` are traced arguments, so jit's own cache keys their
    shapes and dtypes."""
    key = (t_max, trace_err)
    program = engine._spmd_programs.get(key)
    if program is not None:
        return program

    def local_fn(cov, q0, sched, tab, qt):
        # cov/q0: (1, d, d) / (1, d, r) local blocks; sched/tab/qt
        # replicated; scopes as in _sync_outer_body
        def outer(q, tc):
            with jax.named_scope("sdot.apply"):
                z = jnp.matmul(cov[0], q, precision=PRECISION)
            with jax.named_scope("sdot.gossip"):
                z = engine.gossip_rounds_masked(z, tc, t_max)
            with jax.named_scope("sdot.debias"):
                z = engine.debias_by_table(z, tab, tc)
            with jax.named_scope("sdot.qr"):
                q_new = cholesky_qr2(z)[0]
            with jax.named_scope("sdot.error"):
                err = (jax.lax.pmean(subspace_error(qt, q_new), engine.axis)
                       if trace_err else jnp.float32(0.0))
            return q_new, err

        qf, errs = jax.lax.scan(outer, q0[0], sched)
        return qf[None], errs

    spec, rep = P(engine.axis), P()
    program = jax.jit(jax.shard_map(local_fn, mesh=engine.mesh,
                                    in_specs=(spec, spec, rep, rep, rep),
                                    out_specs=(spec, rep)))
    engine._spmd_programs[key] = program
    return program


def sdot_spmd(
    *,
    covs: jnp.ndarray,
    engine,                                   # consensus.SpmdConsensus
    r: int,
    t_outer: int,
    schedule: Optional[np.ndarray] = None,
    t_c: int = 50,
    q_init: Optional[jnp.ndarray] = None,
    q_true: Optional[jnp.ndarray] = None,
    seed: int = 0,
) -> SDOTResult:
    """Whole-run S-DOT/SA-DOT as ONE compiled SPMD program over a mesh axis.

    The node == pod execution mode: node i's covariance block lives on mesh
    position i along ``engine.axis`` and the entire t_outer loop — local
    apply, masked collective gossip (``SpmdConsensus.gossip_rounds_masked``:
    weighted ppermute rounds on a ring, all_gather + local mix otherwise),
    the device debias-table row gather, per-node CholeskyQR2, and the
    pmean'd error trace — runs inside a single jitted shard_map. One compile
    and one dispatch per run instead of one collective chain per outer
    iteration; numerically identical to the fused ``DenseConsensus`` run
    for the same W (tests/test_spmd.py pins it).

    The compiled program is built once per engine and (``t_max``, whether
    ``q_true`` is given) by ``_spmd_program``; later calls with the same
    shapes reuse it without tracing again.

    Host spans as in ``sdot``: ``sdot_spmd.solve`` (with ``rounds_run`` and
    ``rounds_needed``) holds ``sdot_spmd.prepare`` and ``sdot_spmd.call``,
    the program's dispatch, whose ``jit_miss`` counts the calls that missed
    its jit cache (traced, then compiled or loaded from the compile cache).
    """
    n = engine.n
    if covs.shape[0] != n:
        raise ValueError("covs leading dim must equal the mesh axis size")
    with trace_span("sdot_spmd.solve") as span:
        with trace_span("sdot_spmd.prepare"):
            d = covs.shape[1]
            if schedule is None:
                schedule = consensus_schedule("const", t_outer, t_max=t_c)
            elif len(schedule) < t_outer:
                raise ValueError(f"schedule has {len(schedule)} entries but "
                                 f"t_outer={t_outer}")
            sched_np = np.asarray(schedule[:t_outer])
            t_max = int(sched_np.max()) if t_outer else 0
            if q_init is None:
                q_init = orthonormal_init(jax.random.PRNGKey(seed), d, r)
            q_nodes = jnp.broadcast_to(q_init[None], (n, d, r))
            trace_err = q_true is not None
            q_arg = q_true if trace_err else jnp.zeros((d, r), jnp.float32)
            table = engine.debias_table(t_max)
            sched_dev = jnp.asarray(sched_np, jnp.int32)
        span.count(rounds_run=t_outer * t_max,
                   rounds_needed=int(sched_np.sum()))

        with trace_span("sdot_spmd.call") as call:
            program = _spmd_program(engine, t_max, trace_err)
            n_compiled = program._cache_size()
            q_nodes, errs = program(covs, q_nodes, sched_dev, table, q_arg)
            call.count(jit_miss=int(program._cache_size() > n_compiled))

        ledger = CommLedger()
        ledger.log_gossip_rounds(sched_np, engine.graph.adjacency, d * r)
        return SDOTResult(
            q_nodes=q_nodes,
            error_trace=np.asarray(errs) if trace_err else None,
            consensus_trace=sched_np,
            ledger=ledger,
        )
