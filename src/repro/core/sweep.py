"""Monte-Carlo sweep engine over the unified executor runtime.

The paper's Figs. 4-6 are Monte-Carlo averages over random initializations
(and the tables sweep topologies and consensus schedules). Each sweep here
is ONE ``runtime.Program`` with a stacked case axis (topology x schedule)
on its operands and (case, seed) lane axes on its carry —
``runtime.run_sweep`` vmaps the family's OWN scan body over the grid, so a
full sweep compiles once and runs in one device call:

* the **seed axis** vmaps per-seed orthonormal inits;
* the **case axis** vmaps the stacked weight matrices, debias tables, and
  schedule arrays — all dense (N, N) / (t_max+1, N) / (T_o,) arrays, so
  heterogeneous graphs stack as long as they share the node count;
* **ragged node counts** (the Table-II connectivity axis: ER N=10 next to
  ring N=20) stack too: ``sdot_sweep`` / ``baseline_sweep`` (dsa / dpgd /
  deepca) pad each per-case cov stack to N_max with *isolated identity
  nodes* (block-diag(W, I) weights, identity covs, node-masked error
  trace); ``fdot_sweep`` pads per-case slab lists with *all-zero slabs*,
  exact no-ops in every product of Alg. 2, so no mask is needed. See
  ``sweep_utils`` for why the padding is exact; padded traces match the
  unpadded per-case runs bit-comparably.

Because sweeps are ordinary runtime Programs they inherit the chunked
driver for free: pass ``manager``/``chunk_size`` and the sweep-RunState
checkpoints at chunk boundaries — a killed multi-day sweep worker resumes
MID-GRID, bitwise equal to the uninterrupted sweep
(``streaming/worker.py`` runs exactly this path).

Compare: the eager zoo runs seeds x cases x t_outer Python iterations with
a host sync each — the sweep engine runs one dispatch total and the whole
(C, S, T_o) error tensor comes back in one transfer (tests/
test_fused_zoo.py pins sweep == per-seed fused runs).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import runtime
from .baselines import (_d_pm_build_body, _deepca_build_body,
                        _dpgd_build_body, _dsa_build_body,
                        _seq_dist_pm_build_body)
from .consensus import DenseConsensus, consensus_schedule, debias_table
from .fdot import _fdot_build_body, pad_feature_slabs, split_pad_rows
from .linalg import orthonormal_init
from .metrics import CommLedger
from .sdot import _sdot_build_body, _stack_data
from .sweep_utils import (broadcast_per_case, case_node_masks,
                          pad_covs_identity, pad_weights_identity,
                          pad_zero_nodes)

__all__ = ["SweepResult", "sdot_sweep", "fdot_sweep", "baseline_sweep",
           "netfault_sweep", "slice_seed_shards"]


def slice_seed_shards(seeds: Sequence[int], n_shards: int) -> list:
    """Slice the Monte-Carlo seed axis into contiguous lease-granular shards.

    This is the fleet's unit of work (and of fault tolerance): each shard
    is one vmap lane-slice a worker computes, checkpoints, and publishes
    independently, so the multi-host launcher can retry, steal, or
    re-assign shards without touching the others. Contiguity is what makes
    the merged sweep equal the single-process sweep — concatenating the
    shard results along the seed axis preserves seed order exactly.
    ``n_shards`` may exceed the worker count (finer stealing granularity);
    it is clamped to the seed count so no shard is empty."""
    seeds = [int(s) for s in seeds]
    n_shards = max(1, min(int(n_shards), len(seeds)))
    return [list(map(int, s))
            for s in np.array_split(np.asarray(seeds), n_shards)]


@dataclasses.dataclass
class SweepResult:
    """Stacked outputs of a Monte-Carlo sweep.

    ``q`` and ``error_traces`` carry a leading case axis C (only when the
    sweep ran multiple topology/schedule cases) and a seed axis S.

    ``node_counts`` is set by ragged-N sweeps: ``q[c]`` then has node axis
    N_max and only the first ``node_counts[c]`` entries are real (the rest
    are the isolated identity-padding nodes).

    ``steps_done`` counts completed outer iterations (< t_outer only for a
    chunked sweep killed mid-grid; traces cover the completed prefix) and
    ``resumed_step`` is the outer step the restored sweep-RunState carried
    (0 = fresh). ``resume_report`` is filled by the multi-host launcher
    when resuming a workdir: reused shards + per-worker resumed steps.
    """

    q: jnp.ndarray                 # (C?, S, ...) final estimates
    error_traces: Optional[np.ndarray]   # (C?, S, T) per-seed error traces
    ledger: CommLedger             # aggregate communication over all runs
    seeds: np.ndarray
    node_counts: Optional[np.ndarray] = None
    steps_done: Optional[int] = None
    resumed_step: int = 0
    resume_report: Optional[dict] = None

    def _traces(self) -> np.ndarray:
        if self.error_traces is None:
            raise ValueError("sweep ran without q_true — no error traces "
                             "were recorded")
        return self.error_traces

    @property
    def mean_trace(self) -> np.ndarray:
        """Monte-Carlo mean over the seed axis."""
        return self._traces().mean(axis=-2)

    @property
    def std_trace(self) -> np.ndarray:
        return self._traces().std(axis=-2)

    @classmethod
    def merge_shards(cls, trees: Sequence[dict], *, n_cases: int,
                     has_err: bool, ragged: bool,
                     resume_report: Optional[dict] = None) -> "SweepResult":
        """Merge per-shard result trees along the seed axis.

        ``trees`` are the published shard payloads (``q``, ``seeds``,
        ``ledger``, optional ``error_traces`` / ``node_counts``) in shard
        order — contiguous seed slices from ``slice_seed_shards``, so
        concatenation reproduces the single-process sweep's seed order
        exactly and the merged result is arithmetically identical to it
        (bitwise when the shard lane widths match). The merge is NumPy
        only, so a launcher can merge without opening a JAX backend.

        Two classes of operator error are rejected instead of silently
        concatenated: shards published under DIFFERENT spec fingerprints
        (e.g. a workdir reused across sweep configurations), and shards
        whose seed slices OVERLAP (e.g. mixing shard files from two
        different ``n_shards`` partitionings of the same seed list) —
        either would yield a merged result that matches no single-process
        sweep."""
        fps = sorted({int(np.asarray(tree["spec_fp"])) for tree in trees
                      if "spec_fp" in tree})
        if len(fps) > 1:
            raise ValueError(
                "merge_shards: shards come from different sweep specs "
                f"(spec fingerprints {fps}) — refusing to merge results "
                "of different configurations")
        seen = {}
        for i, tree in enumerate(trees):
            for s in np.asarray(tree["seeds"]).reshape(-1).tolist():
                s = int(s)
                if s in seen:
                    raise ValueError(
                        f"merge_shards: seed {s} appears in shard "
                        f"{seen[s]} and shard {i} — overlapping seed "
                        "slices (mixed shard partitionings?)")
                seen[s] = i
        seed_axis = 1 if n_cases > 1 else 0
        qs, errs, counts, node_counts = [], [], [], None
        ledger = CommLedger()
        for tree in trees:
            qs.append(np.asarray(tree["q"]))
            counts.append(np.asarray(tree["seeds"]))
            ledger = ledger.merged(tree["ledger"])
            if has_err:
                errs.append(np.asarray(tree["error_traces"]))
            if ragged:
                node_counts = np.asarray(tree["node_counts"])
        return cls(
            q=np.concatenate(qs, axis=seed_axis),
            error_traces=(np.concatenate(errs, axis=seed_axis)
                          if has_err else None),
            ledger=ledger,
            seeds=np.concatenate(counts),
            node_counts=node_counts,
            resume_report=resume_report,
        )


def _seed_inits(seeds: Sequence[int], d: int, r: int) -> jnp.ndarray:
    """(S, d, r) orthonormal inits, one per Monte-Carlo seed (vmapped QR)."""
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    return jax.vmap(lambda k: orthonormal_init(k, d, r))(keys)


def _broadcast_cases(engines, schedules, t_outer, t_c, allow_ragged=False):
    """Zip-broadcast engines x schedules into C aligned cases."""
    if isinstance(engines, DenseConsensus):
        engines = [engines]
    engines = list(engines)
    if schedules is None:
        schedules = [consensus_schedule("const", t_outer, t_max=t_c)]
    elif isinstance(schedules, np.ndarray) and schedules.ndim == 1:
        schedules = [schedules]
    schedules = [np.asarray(s) for s in schedules]
    for s in schedules:
        if len(s) < t_outer:
            raise ValueError(f"schedule has {len(s)} entries but "
                             f"t_outer={t_outer}")
    c = max(len(engines), len(schedules))
    if len(engines) == 1:
        engines = engines * c
    if len(schedules) == 1:
        schedules = schedules * c
    if len(engines) != len(schedules):
        raise ValueError("engines and schedules must zip-broadcast: got "
                         f"{len(engines)} vs {len(schedules)}")
    n_nodes = engines[0].graph.n_nodes
    if not allow_ragged and any(e.graph.n_nodes != n_nodes for e in engines):
        raise ValueError("all sweep engines must share the node count")
    return engines, [s[:t_outer] for s in schedules]


def _reject_sparse(engines) -> None:
    """Sweep fleets vmap over dense (C, N, N) weight stacks; sparse
    engines (``SparseW`` mixing) are not sweepable yet — fail with a
    clear message instead of a pytree-stacking TypeError deep in jnp."""
    if any(getattr(e, "is_sparse", False) for e in engines):
        raise ValueError(
            "sweeps require dense engines: construct with sparse=False "
            "(SparseW-backed engines are not vmappable across cases yet)")


def _case_stacks(engines, t_max):
    _reject_sparse(engines)
    ws = jnp.stack([e._w for e in engines])
    tables = jnp.stack([e.debias_table(t_max) for e in engines])
    return ws, tables


def _ragged_stacks(engines, t_max):
    """Identity-padded (C, N_max, N_max) weights + debias tables + masks for
    a mixed-node-count case axis."""
    _reject_sparse(engines)
    n_list = [e.graph.n_nodes for e in engines]
    n_max = max(n_list)
    ws = jnp.stack([jnp.asarray(pad_weights_identity(e.weights, n_max))
                    for e in engines])
    tables = jnp.stack([debias_table(w, t_max) for w in ws])
    masks = case_node_masks(n_list, n_max)                   # (C, N_max)
    return ws, tables, masks, n_list, n_max


def _check_case_covs(case_covs, engines):
    for c, e in zip(case_covs, engines):
        if c.shape[0] != e.graph.n_nodes:
            raise ValueError("per-case covs must match each engine's node "
                             f"count: got {c.shape[0]} covs for an "
                             f"{e.graph.n_nodes}-node graph")


def _squeeze_case(arr, single_case: bool):
    return arr[0] if single_case else arr


def _lane_q0(q0, n_cases: int):
    """Broadcast (S, ...) per-seed carry leaves to (C, S, ...) lanes."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n_cases,) + a.shape), q0)


def _sweep_result(state, done, *, q_map, trace_err, single_case, ledger,
                  seeds, node_counts=None):
    errs = state.errs[..., :done]
    return SweepResult(
        q=_squeeze_case(q_map(state.q), single_case),
        error_traces=(np.asarray(_squeeze_case(errs, single_case))
                      if trace_err else None),
        ledger=ledger,
        seeds=np.asarray(list(seeds)),
        node_counts=node_counts,
        steps_done=done,
    )


def _run_sweep(build, operands, statics, xs, q0, case_axes, n_cases,
               n_seeds, finalize, manager, chunk_size, max_chunks,
               key0=None, tail=()):
    """Assemble the sweep Program and hand it to the runtime driver."""
    program = runtime.Program(
        build_body=build, operands=operands, statics=statics, xs=xs, q0=q0,
        key0=key0, tail=tail, case_axes=case_axes, n_cases=n_cases,
        n_seeds=n_seeds, finalize=finalize)
    result = runtime.run_sweep(program, manager=manager,
                               chunk_size=chunk_size, max_chunks=max_chunks)
    result.resumed_step = program.restored_step
    return result


def sdot_sweep(
    *,
    covs=None,
    data: Optional[Sequence[jnp.ndarray]] = None,
    engines: Union[DenseConsensus, Sequence[DenseConsensus]],
    r: int,
    t_outer: int,
    schedules=None,
    t_c: int = 50,
    seeds: Sequence[int] = (0,),
    q_true: Optional[jnp.ndarray] = None,
    manager=None,
    chunk_size: Optional[int] = None,
    max_chunks: Optional[int] = None,
) -> SweepResult:
    """Monte-Carlo S-DOT/SA-DOT sweep: seeds x (topology, schedule) cases in
    one compile + one device call.

    ``engines`` / ``schedules`` zip-broadcast into the case axis (pass one
    engine and k schedules, k engines and one schedule, or aligned lists).
    Each seed gets its own orthonormal init (the paper's Monte-Carlo axis).
    ``covs`` is one (N, d, d) stack shared by every case, or a list with
    one (N_c, d, d) stack per case (mixed node counts pad with isolated
    identity nodes — see the module docstring — and the result carries
    ``node_counts``). ``manager``/``chunk_size`` run the sweep through the
    chunked driver: the sweep-RunState checkpoints at chunk boundaries and
    a killed sweep (``max_chunks``) resumes mid-grid, bitwise equal to the
    uninterrupted sweep.
    """
    if (covs is None) == (data is None):
        raise ValueError("provide exactly one of covs / data")
    per_case_covs = covs is not None and isinstance(covs, (list, tuple))
    engines, schedules = _broadcast_cases(engines, schedules, t_outer, t_c,
                                          allow_ragged=per_case_covs)
    single_case = len(engines) == 1
    t_max = int(max(int(s.max()) for s in schedules)) if t_outer else 0
    trace_err = q_true is not None

    if per_case_covs:
        case_covs = broadcast_per_case([jnp.asarray(c) for c in covs],
                                       len(engines), "covs")
        _check_case_covs(case_covs, engines)
        d = int(case_covs[0].shape[1])
        ws, tables, masks, n_list, n_max = _ragged_stacks(engines, t_max)
        covs_pad = jnp.stack([pad_covs_identity(c, n_max)
                              for c in case_covs])           # (C, N_max, d, d)
        q_arg = q_true if trace_err else jnp.zeros((d, r), jnp.float32)
        operands = (covs_pad, ws, tables, q_arg, masks)
        case_axes = (0, 0, 0, None, 0)
        mode, n = "cov", n_max
        node_counts = np.asarray(n_list)
    else:
        n = engines[0].graph.n_nodes
        d = covs.shape[1] if covs is not None else data[0].shape[0]
        ws, tables = _case_stacks(engines, t_max)
        masks = jnp.ones((len(engines), n), jnp.float32)
        q_arg = q_true if trace_err else jnp.zeros((d, r), jnp.float32)
        if covs is not None:
            operands = (covs, ws, tables, q_arg, masks)
            case_axes = (None, 0, 0, None, 0)
            mode = "cov"
        else:
            x_stack, n_true, _ = _stack_data(data, r)
            operands = (x_stack, n_true, ws, tables, q_arg, masks)
            case_axes = (None, None, 0, 0, None, 0)
            mode = "data"
        node_counts = None

    q0 = _seed_inits(seeds, d, r)                            # (S, d, r)
    q0_nodes = jnp.broadcast_to(q0[:, None], (len(seeds), n, d, r))

    ledger = CommLedger()
    payload = d * r

    def finalize(state, done):
        for eng, sched in zip(engines, schedules):
            for _ in seeds:
                ledger.log_gossip_rounds(sched[:done], eng.graph.adjacency,
                                         payload)
        return _sweep_result(state, done, q_map=lambda q: q,
                             trace_err=trace_err, single_case=single_case,
                             ledger=ledger, seeds=seeds,
                             node_counts=node_counts)

    return _run_sweep(
        _sdot_build_body, operands,
        (("mode", mode), ("t_max", t_max), ("trace_err", trace_err),
         ("is_async", False)),
        np.stack(schedules).astype(np.int64), _lane_q0(q0_nodes, len(engines)),
        case_axes, len(engines), len(list(seeds)), finalize,
        manager, chunk_size, max_chunks)


def netfault_sweep(
    *,
    covs,
    engines,
    r: int,
    t_outer: int,
    schedules=None,
    t_c: int = 50,
    seeds: Sequence[int] = (0,),
    q_true: Optional[jnp.ndarray] = None,
    manager=None,
    chunk_size: Optional[int] = None,
    max_chunks: Optional[int] = None,
) -> SweepResult:
    """Monte-Carlo S-DOT/SA-DOT sweep under network faults: seeds x
    (FaultyConsensus, schedule) cases in one compile + one device call.

    The case axis is a FAULT grid: each case is a ``FaultyConsensus``
    engine whose scalar fault knobs stack as (C, 6) lane data and whose
    crash windows lower to a (C, T, N) node-up stack — sweeping link-drop
    rate, burst length, or crash fraction recompiles NOTHING (one body, C
    lanes), which is what makes the degradation curves of
    ``benchmarks/netfaults_bench.py`` cheap. Per-lane RNG keys are derived
    by folding each seed VALUE into each case engine's key, so a sweep
    shard computes bitwise the same lanes whether it runs alone or inside
    the full grid (shard-merge independence, the fleet's requirement).
    All case engines must share the node count and the ``debias`` mode
    (``debias`` is a compile-time static of the shared body).

    ``manager``/``chunk_size`` run the sweep through the chunked driver —
    the Gilbert–Elliott state and iteration counter ride in the
    checkpointed carry, so a killed faulty sweep resumes mid-grid bitwise
    equal to the uninterrupted one.
    """
    if not isinstance(engines, (list, tuple)):
        engines = [engines]
    for e in engines:
        if not hasattr(e, "sample_faults"):
            raise ValueError("netfault_sweep needs FaultyConsensus engines")
    engines, schedules = _broadcast_cases(list(engines), schedules, t_outer,
                                          t_c)
    debias = engines[0].debias
    if any(e.debias != debias for e in engines):
        raise ValueError("all netfault_sweep engines must share the debias "
                         "mode (it is a compile-time static)")
    single_case = len(engines) == 1
    n = engines[0].graph.n_nodes
    d = covs.shape[1]
    t_max = int(max(int(s.max()) for s in schedules)) if t_outer else 0
    trace_err = q_true is not None
    s_list = [int(s) for s in seeds]

    _reject_sparse(engines)
    ws = jnp.stack([e._w for e in engines])
    adjs = jnp.stack([e._adj for e in engines])
    params = jnp.stack([e._params for e in engines])          # (C, 6)
    node_up = jnp.stack([
        jnp.asarray(e.faults.validate(n, t_outer).node_up(t_outer, n))
        for e in engines])                                    # (C, T, N)
    tables = jnp.stack([debias_table(e._w, t_max) for e in engines])
    q_arg = q_true if trace_err else jnp.zeros((d, r), jnp.float32)
    operands = (covs, ws, adjs, params, node_up, tables, q_arg)
    case_axes = (None, 0, 0, 0, 0, 0, None)

    q0 = _seed_inits(s_list, d, r)                            # (S, d, r)
    q0_nodes = jnp.broadcast_to(q0[:, None], (len(s_list), n, d, r))
    ge0 = jnp.zeros((len(s_list), n, n), bool)
    t0 = jnp.zeros((len(s_list),), jnp.int32)
    q0_lane = _lane_q0((q0_nodes, ge0, t0), len(engines))
    # per-lane keys: fold the seed VALUE (not its grid position) into each
    # case engine's key — a shard covering seeds [2, 3] derives exactly the
    # lanes the full grid derives at those seeds
    key0 = jnp.stack([
        jnp.stack([jax.random.fold_in(e._key, s) for s in s_list])
        for e in engines])                                    # (C, S, 2)

    payload = d * r
    sched_stack = np.stack(schedules)

    def finalize(state, done):
        ledger = CommLedger()
        sends = np.asarray(state.sends[..., :done, :], np.float64)
        counts = np.asarray(state.counts[..., :done, :])
        total = float(sends.sum())
        ledger.p2p += total
        ledger.matrices += total
        ledger.scalars += total * payload
        for c in range(len(engines)):
            for s_i in range(len(s_list)):
                for t in range(done):
                    ledger.log_awake_rounds(
                        counts[c, s_i, t][:int(sched_stack[c][t])])
        return _sweep_result(state, done, q_map=lambda q: q[0],
                             trace_err=trace_err, single_case=single_case,
                             ledger=ledger, seeds=s_list)

    return _run_sweep(
        _sdot_build_body, operands,
        (("mode", "cov"), ("t_max", t_max), ("trace_err", trace_err),
         ("is_async", False), ("is_faulty", True), ("debias", debias)),
        sched_stack.astype(np.int64), q0_lane,
        case_axes, len(engines), len(s_list), finalize,
        manager, chunk_size, max_chunks, key0=key0, tail=(t_max,))


def fdot_sweep(
    *,
    data_blocks: Sequence,
    engines: Union[DenseConsensus, Sequence[DenseConsensus]],
    r: int,
    t_outer: int,
    schedules=None,
    t_c: int = 50,
    t_c_qr: Optional[int] = None,
    seeds: Sequence[int] = (0,),
    q_true: Optional[jnp.ndarray] = None,
    manager=None,
    chunk_size: Optional[int] = None,
    max_chunks: Optional[int] = None,
) -> SweepResult:
    """Monte-Carlo F-DOT sweep over padded feature slabs (Fig. 6 axis).

    ``data_blocks`` is one slab list shared by every case, or a list of
    slab *lists* with one per case (mixed node counts — different
    partitionings of the same d features — pad with all-zero slabs, exact
    no-ops in every product of Alg. 2, and the result carries
    ``node_counts``). ``manager``/``chunk_size`` enable the
    chunked-resumable driver, as in ``sdot_sweep``.
    """
    per_case = (len(data_blocks) > 0
                and isinstance(data_blocks[0], (list, tuple)))
    engines, schedules = _broadcast_cases(engines, schedules, t_outer, t_c,
                                          allow_ragged=per_case)
    single_case = len(engines) == 1
    t_c_qr = int(t_c if t_c_qr is None else t_c_qr)
    passes = 2
    t_max = int(max(max(int(s.max()) for s in schedules), t_c_qr))
    trace_err = q_true is not None

    if per_case:
        case_blocks = broadcast_per_case(data_blocks, len(engines),
                                         "data_blocks")
        n_list = []
        for blocks, e in zip(case_blocks, engines):
            if len(blocks) != e.graph.n_nodes:
                raise ValueError("per-case data_blocks must match each "
                                 f"engine's node count: got {len(blocks)} "
                                 f"slabs for an {e.graph.n_nodes}-node graph")
            n_list.append(e.graph.n_nodes)
        case_dims = [[int(x.shape[0]) for x in blocks]
                     for blocks in case_blocks]
        d = sum(case_dims[0])
        if any(sum(dims) != d for dims in case_dims):
            raise ValueError("every case must partition the same d features")
        n_samples = int(case_blocks[0][0].shape[1])
        ws, tables, _, _, n_max = _ragged_stacks(engines, t_max)
        d_slab = max(max(dims) for dims in case_dims)
        pad_case = lambda stack: pad_zero_nodes(
            jnp.pad(stack, ((0, 0), (0, d_slab - stack.shape[1]), (0, 0))),
            n_max)
        x_pad = jnp.stack([pad_case(pad_feature_slabs(blocks))
                           for blocks in case_blocks])  # (C, N_max, d_slab, n)
        q_seeds = _seed_inits(seeds, d, r)
        q0_pad = jnp.stack([
            jnp.stack([pad_case(split_pad_rows(q, dims)) for q in q_seeds])
            for dims in case_dims])                      # (C, S, N_max, ..)
        qtrue_pad = jnp.stack([
            (pad_case(split_pad_rows(q_true, dims)) if trace_err
             else jnp.zeros((n_max, d_slab, r), jnp.float32))
            for dims in case_dims])                      # (C, N_max, d_slab, r)
        operands = (x_pad, ws, tables, qtrue_pad)
        case_axes = (0, 0, 0, 0)
        node_counts = np.asarray(n_list)
    else:
        n_nodes = engines[0].graph.n_nodes
        if len(data_blocks) != n_nodes:
            raise ValueError("need one feature slab per node")
        dims = [int(x.shape[0]) for x in data_blocks]
        d = sum(dims)
        n_samples = int(data_blocks[0].shape[1])
        ws, tables = _case_stacks(engines, t_max)

        x_pad = pad_feature_slabs(data_blocks)
        q0_seed = jnp.stack([split_pad_rows(q, dims)
                             for q in _seed_inits(seeds, d, r)])
        qtrue_pad = (split_pad_rows(q_true, dims) if trace_err
                     else jnp.zeros_like(q0_seed[0]))
        q0_pad = _lane_q0(q0_seed, len(engines))
        operands = (x_pad, ws, tables, qtrue_pad)
        case_axes = (None, 0, 0, None)
        node_counts = None

    ledger = CommLedger()

    def finalize(state, done):
        for eng, sched in zip(engines, schedules):
            for _ in seeds:
                ledger.log_gossip_rounds(sched[:done], eng.graph.adjacency,
                                         n_samples * r)
                ledger.log_gossip_rounds(np.full(done, passes * t_c_qr),
                                         eng.graph.adjacency, r * r)
        return _sweep_result(state, done, q_map=lambda q: q,
                             trace_err=trace_err, single_case=single_case,
                             ledger=ledger, seeds=seeds,
                             node_counts=node_counts)

    return _run_sweep(
        _fdot_build_body, operands,
        (("t_max", t_max), ("t_c_qr", t_c_qr), ("passes", passes),
         ("trace_err", trace_err), ("is_async", False)),
        np.stack(schedules).astype(np.int64), q0_pad,
        case_axes, len(engines), len(list(seeds)), finalize,
        manager, chunk_size, max_chunks)


def baseline_sweep(
    name: str,
    *,
    covs=None,
    data_blocks: Optional[Sequence[jnp.ndarray]] = None,
    engine: Optional[DenseConsensus] = None,
    engines=None,
    r: int,
    seeds: Sequence[int] = (0,),
    q_true: Optional[jnp.ndarray] = None,
    t_outer: Optional[int] = None,
    iters_per_vec: Optional[int] = None,
    lr: float = 0.1,
    t_mix: int = 3,
    t_c: int = 50,
    manager=None,
    chunk_size: Optional[int] = None,
    max_chunks: Optional[int] = None,
) -> SweepResult:
    """Monte-Carlo sweep of one fused baseline over seeds (one device call).

    ``name``: dsa | dpgd | deepca (sample-partitioned, need ``covs`` +
    ``t_outer``), seq_dist_pm (``covs`` + ``iters_per_vec``), or d_pm
    (feature-partitioned, ``data_blocks`` + ``iters_per_vec``).

    The cov-based trio also accepts ``engines`` (a list) plus per-case
    ``covs`` (a list of (N_c, d, d) stacks) with mixed node counts — the
    same ragged-N identity-padding contract as ``sdot_sweep``; the result
    then carries a case axis and ``node_counts``. The sequential-deflation
    baselines (seq_dist_pm, d_pm) are single-case only.
    ``manager``/``chunk_size`` enable the chunked-resumable driver, as in
    ``sdot_sweep``.
    """
    if engines is not None and engine is not None:
        raise ValueError("pass engine or engines, not both")
    engine_list = None
    if engines is not None:
        if isinstance(engines, DenseConsensus):
            engine = engines
        else:
            engine_list = list(engines)
    if engine is None and engine_list is None:
        raise ValueError("baseline_sweep needs an engine")

    trace_err = q_true is not None
    s_count = len(list(seeds))
    node_counts = None
    squeeze_node_counts = False

    if engine_list is not None:
        if name not in ("dsa", "dpgd", "deepca"):
            raise ValueError(f"{name} does not support a ragged-N case axis "
                             "(sequential-deflation baselines are "
                             "single-case only)")
        if covs is None or t_outer is None:
            raise ValueError(f"{name} sweep needs covs and t_outer")
        if not isinstance(covs, (list, tuple)):
            covs = [covs]
        case_covs = broadcast_per_case([jnp.asarray(c) for c in covs],
                                       len(engine_list), "covs")
        _check_case_covs(case_covs, engine_list)
        ws, _, masks, n_list, n_max = _ragged_stacks(engine_list, 0)
        case_covs = jnp.stack([pad_covs_identity(c, n_max)
                               for c in case_covs])      # (C, N_max, d, d)
        node_counts = np.asarray(n_list)
        squeeze_node_counts = len(engine_list) == 1
    else:
        engine_list = [engine]
        if name in ("dsa", "dpgd", "deepca"):
            if covs is None or t_outer is None:
                raise ValueError(f"{name} sweep needs covs and t_outer")
        _reject_sparse(engine_list)
        ws = jnp.stack([engine._w])
        n_max = engine.graph.n_nodes
        masks = jnp.ones((1, n_max), jnp.float32)
        if covs is not None:
            case_covs = jnp.stack([jnp.asarray(covs)])   # (1, N, d, d)

    n_cases = len(engine_list)
    single_case = n_cases == 1
    ledger = CommLedger()

    if name in ("dsa", "dpgd", "deepca"):
        d = int(case_covs.shape[2])
        q_arg = q_true if trace_err else jnp.zeros((d, r), jnp.float32)
        q0 = _seed_inits(seeds, d, r)
        q0_nodes = jnp.broadcast_to(q0[:, None], (s_count, n_max, d, r))
        q0_lane = _lane_q0(q0_nodes, n_cases)            # (C, S, N_max, d, r)
        xs = np.zeros((n_cases, t_outer), np.int64)
        if name == "deepca":
            build = _deepca_build_body
            statics = (("t_mix", t_mix), ("trace_err", trace_err))
            # s0 = M_i Q_i per (case, seed) lane, over the padded cov stacks
            s0 = jnp.einsum("cnde,csner->csndr", case_covs, q0_lane)
            q0_lane = (q0_lane, s0, s0)
            operands = (case_covs, ws, q_arg, masks)
            case_axes = (0, 0, None, 0)
            rounds = lambda done: np.full(done, t_mix)
        else:
            build = _dsa_build_body if name == "dsa" else _dpgd_build_body
            statics = (("trace_err", trace_err),)
            operands = (case_covs, ws, jnp.float32(lr), q_arg, masks)
            case_axes = (0, 0, None, None, 0)
            rounds = lambda done: np.ones(done)
        q_map = (lambda c: c[0]) if name == "deepca" else (lambda q: q)
        payload = d * r
    elif name in ("seq_dist_pm", "d_pm"):
        if iters_per_vec is None or (covs is None) == (data_blocks is None):
            raise ValueError(f"{name} sweep needs iters_per_vec and "
                             "covs (seq_dist_pm) / data_blocks (d_pm)")
        statics = (("r", r), ("iters_per_vec", iters_per_vec),
                   ("t_c", t_c), ("t_max", t_c), ("trace_err", trace_err))
        case_axes = (None, 0, 0, None)
        xs = np.arange(r * iters_per_vec, dtype=np.int64)[None]
        rounds = lambda done: np.full(done, t_c)
        tables = jnp.stack([engine.debias_table(t_c)])
        if name == "seq_dist_pm":
            n, d, _ = covs.shape
            q_arg = q_true if trace_err else jnp.zeros((d, r), jnp.float32)
            cols0 = jnp.broadcast_to(
                jnp.swapaxes(_seed_inits(seeds, d, r), 1, 2)[:, :, None, :],
                (s_count, r, n, d))
            q0_lane = _lane_q0(cols0, 1)
            build = _seq_dist_pm_build_body
            operands = (covs, ws, tables, q_arg)
            q_map = lambda cols: jnp.transpose(cols, (0, 1, 3, 4, 2))
            payload = d
        else:
            dims = [int(x.shape[0]) for x in data_blocks]
            d = sum(dims)
            x_pad = pad_feature_slabs(data_blocks)
            q0_pad = jnp.stack([split_pad_rows(q, dims)
                                for q in _seed_inits(seeds, d, r)])
            q0_lane = _lane_q0(jnp.transpose(q0_pad, (0, 3, 1, 2)), 1)
            qtrue_pad = (split_pad_rows(q_true, dims) if trace_err
                         else jnp.zeros_like(q0_pad[0]))
            build = _d_pm_build_body
            operands = (x_pad, ws, tables, qtrue_pad)
            # blocks: (C, S, r, N, d_max) -> concatenated (C, S, d, r)
            q_map = lambda blocks: jnp.concatenate(
                [jnp.swapaxes(blocks[:, :, :, i, :di], 2, 3)
                 for i, di in enumerate(dims)], axis=2)
            payload = int(data_blocks[0].shape[1])       # n_samples
    else:
        raise ValueError(f"unknown baseline: {name}")

    def finalize(state, done):
        for eng in engine_list:
            for _ in range(s_count):
                ledger.log_gossip_rounds(rounds(done), eng.graph.adjacency,
                                         payload)
        return _sweep_result(
            state, done, q_map=q_map, trace_err=trace_err,
            single_case=single_case, ledger=ledger, seeds=seeds,
            node_counts=(None if squeeze_node_counts else node_counts))

    return _run_sweep(build, operands, statics, xs, q0_lane, case_axes,
                      n_cases, s_count, finalize, manager, chunk_size,
                      max_chunks)
