"""Multi-host Monte-Carlo sweep launcher with chaos-hardened supervision.

``core/sweep.py`` collapses a seeds x cases grid into one compiled program —
for one process.  This module shards that grid over *hosts* (subprocess
workers standing in for hosts in this container; the same spec/result
protocol maps onto one job per machine on a real fleet):

    launch_sweep(...)
      -> writes <workdir>/spec.json (topologies, schedules, shard seed
         lists — everything a worker needs to rebuild its slice) and
         <workdir>/problem.npz (cov stacks, optional ground truth)
      -> runs the case x seed grid as ``n_shards`` leasable shards
         (``core.sweep.slice_seed_shards``) over ``n_workers`` subprocess
         workers; each worker publishes its shard result atomically
         (checkpoint/manager.save_tree, CommLedger riding along as a
         registered pytree) into <workdir>/worker_<shard>/
      -> gathers the shard results and merges them along the seed axis
         into ONE SweepResult, equal to the single-process ``sdot_sweep``
         over the full seed list (lane-slices are arithmetically
         identical; XLA may schedule a width-1 vmap differently, so
         equality is pinned at float32 epsilon in tests/test_streaming.py
         and bit-for-bit when shard widths match the full sweep's).

Supervision is a CONCURRENT POLL LOOP, not a serial join: every worker is
polled against one shared deadline, a dead process is detected within one
poll interval, and a wedged-but-alive worker is detected by a stale
heartbeat (workers touch ``worker_<shard>/heartbeat`` at every chunk
boundary) and killed. Failed shards retry under a per-shard budget with
exponential backoff + jitter. A fleet of stragglers can therefore no
longer stall the launcher for ``n_workers x timeout`` — the old serial
``communicate(timeout=...)`` pass charged the full timeout to each worker
in turn.

``elastic=True`` switches to lease-based fleet execution
(``streaming/fleet.py``): workers are not pinned to shards but acquire
lease files (fencing tokens under ``<workdir>/leases/``), a worker that
finishes its shard STEALS the stalest expired lease and resumes the
victim's checkpointed sweep-RunState mid-grid, and membership is elastic —
start another ``python -m repro.streaming.worker <spec> --fleet`` at any
time to join a running sweep; a worker that dies simply lets its lease
expire. Because shard results are deterministic and published atomically,
stealing/duplication never changes the merged bits.

``chaos_plan`` injects a seeded ``streaming.chaos.FaultPlan`` into the
workers (SIGKILL at chunk boundaries, torn checkpoints, stragglers,
dropped results) via the ``REPRO_CHAOS_PLAN`` env var — the CI chaos-smoke
job asserts the merged result under faults equals the fault-free sweep.

Shard-granular fault tolerance: a shard that already published a valid
result is never recomputed (so a killed launcher resumes where it left
off), a crashed shard is retried with backoff, and only then does the
launch fail.

One process per chip: the launcher never opens a JAX backend (it writes
NumPy problem files and restores published shards into NumPy), so every
chip stays free for the workers. A worker opens all the chips of its host,
so on a host with TPU chips the launcher refuses more than one worker at a
time, and a worker that nevertheless finds no TPU exits with
``launch.device.EXIT_NO_DEVICE``, which stops the launch at once.

Topologies/schedules travel as small JSON specs (``build_engine`` /
``build_schedule``) because graph constructions are seed-deterministic —
the paper's experiment grid is fully reproducible from the spec file.
"""
from __future__ import annotations

import hashlib
import json
import os
import random
import shutil
import subprocess
import sys
import time
import zipfile
from typing import Optional, Sequence, Union

import numpy as np

from ..checkpoint.manager import restore_tree
from ..core.consensus import DenseConsensus, consensus_schedule
from ..core.metrics import CommLedger
from ..core.sweep import SweepResult, slice_seed_shards
from ..core.topology import complete, erdos_renyi, ring, star, torus2d
from ..launch.device import EXIT_NO_DEVICE, tpu_chips_on_host, tpu_expected
from ..obs import Journal, obs_dir_for
from .chaos import (ENV_PLAN, FaultPlan, net_faults_from_env,
                    validate_net_fault_doc)
from .fleet import LeaseStore, read_heartbeat

__all__ = ["build_engine", "build_schedule", "launch_sweep"]

_SPEC = "spec.json"
_PROBLEM = "problem.npz"
_CHAOS_PLAN = "chaos_plan.json"

# restore-time failure modes we EXPECT from an absent/stale/torn shard:
# missing files, truncated npz payloads, tree-structure mismatches. Anything
# else is surfaced on the resume report instead of silently recomputed.
_EXPECTED_RESTORE_ERRORS = (OSError, ValueError, KeyError, EOFError,
                            zipfile.BadZipFile)


def build_engine(topo: dict) -> DenseConsensus:
    """Topology spec -> consensus engine (seed-deterministic across hosts)."""
    kind = topo["kind"]
    if kind == "ring":
        g = ring(topo["n"])
    elif kind == "star":
        g = star(topo["n"])
    elif kind == "complete":
        g = complete(topo["n"])
    elif kind == "torus2d":
        g = torus2d(topo["rows"], topo["cols"])
    elif kind == "er":
        g = erdos_renyi(topo["n"], topo["p"], seed=topo.get("seed", 0))
    else:
        raise ValueError(f"unknown topology kind: {kind}")
    return DenseConsensus(g)


def build_schedule(sched: Optional[dict], t_outer: int,
                   t_c: int) -> np.ndarray:
    """Schedule spec -> (t_outer,) consensus budgets."""
    if sched is None:
        return consensus_schedule("const", t_outer, t_max=t_c)
    if "values" in sched:
        return np.asarray(sched["values"])[:t_outer]
    return consensus_schedule(sched["kind"], t_outer,
                              t_max=sched.get("t_max", t_c),
                              cap=sched.get("cap"))


def _worker_dir(workdir: str, shard: int) -> str:
    return os.path.join(workdir, f"worker_{shard}")


def _result_dir(workdir: str, shard: int) -> str:
    return os.path.join(_worker_dir(workdir, shard), "result")


def _heartbeat_path(workdir: str, shard: int) -> str:
    return os.path.join(_worker_dir(workdir, shard), "heartbeat")


def spec_fingerprint(spec: dict) -> int:
    """Stable 31-bit digest of the sweep spec (int32-safe: jax x64 is off).

    Stamped into every worker's published result and checked before a
    shard is reused, so rerunning a workdir with a *changed* spec (more
    seeds, different cases/t_outer) relaunches instead of silently merging
    stale shards. ``sweep_chunk`` is excluded: chunking is bit-exact by
    construction, so a resume may change the chunk size without
    invalidating published shards."""
    blob = json.dumps({k: v for k, v in spec.items() if k != "sweep_chunk"},
                      sort_keys=True).encode()
    return int.from_bytes(hashlib.sha256(blob).digest()[:4], "big") >> 1


def _result_like(spec: dict, with_resumed: bool = True):
    """Structure template for restore_tree (values are ignored)."""
    like = {"q": 0, "seeds": 0, "ledger": CommLedger(), "spec_fp": 0}
    if with_resumed:
        like["resumed_steps"] = 0
    if spec["has_q_true"]:
        like["error_traces"] = 0
    if spec["ragged"]:
        like["node_counts"] = 0
    return like


def _load_result(workdir: str, spec: dict, shard: int,
                 unexpected: Optional[dict] = None):
    """The shard's published result, or None if absent/stale/corrupt.

    A result published under a different spec (stale workdir reuse) fails
    either the tree-structure check or the fingerprint comparison and is
    discarded so the launcher recomputes it. Results published before the
    ``resumed_steps`` leaf existed still restore (never recompute a valid
    shard over a reporting field) and report 0.

    Only the EXPECTED restore failure modes are swallowed; anything else is
    recorded in ``unexpected`` (shard -> repr) so the launcher can surface
    it on the resume report instead of recomputing a possibly-valid shard
    without explanation."""
    path = _result_dir(workdir, shard)
    if not os.path.exists(os.path.join(path, "manifest.json")):
        return None
    tree = None
    for with_resumed in (True, False):
        try:
            tree = restore_tree(path, _result_like(spec, with_resumed),
                                host=True)
            break
        except _EXPECTED_RESTORE_ERRORS:
            continue
        except Exception as e:                   # noqa: BLE001 — surfaced
            if unexpected is not None:
                unexpected[shard] = f"{type(e).__name__}: {e}"
            return None
    if tree is None:
        return None
    if int(tree["spec_fp"]) != spec_fingerprint(spec):
        return None
    tree.setdefault("resumed_steps", 0)
    return tree


def _spawn(args, env, log_path) -> subprocess.Popen:
    """Spawn a worker with stdout+stderr appended to ``log_path`` (a fleet
    can't funnel every worker through launcher pipes — full pipes would
    wedge exactly the workers we are supervising)."""
    os.makedirs(os.path.dirname(log_path), exist_ok=True)
    log = open(log_path, "ab")
    try:
        return subprocess.Popen(
            [sys.executable, "-m", "repro.streaming.worker", *args],
            stdout=log, stderr=log, env=env)
    finally:
        log.close()


def _tail(log_path: str, n: int = 2000) -> str:
    try:
        with open(log_path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")
    except OSError:
        return "<no worker log>"


def _trace_tail(workdir: str, proc: str, n: int = 8) -> str:
    """The worker's journal tail — last spans plus any span left OPEN at
    death — so a failure report says what PHASE the worker died in, not
    just its last stdout lines. Empty-string when tracing is off or the
    worker never journaled."""
    from ..obs.cli import forensics_report
    obs_dir = obs_dir_for(workdir)
    if obs_dir is None or not os.path.isdir(obs_dir):
        return ""
    try:
        text, _ = forensics_report(obs_dir, last=n, proc=proc)
    except Exception:
        return ""
    return text.strip()


def _fail_report(workdir: str, proc: str, log_path: str) -> str:
    """stderr tail + journal tail, the launcher's full failure context."""
    out = f"last log tail:\n{_tail(log_path)}"
    trace = _trace_tail(workdir, proc)
    if trace:
        out += f"\njournal tail ({proc}):\n{trace}"
    return out


def _no_device(rc, workdir: str, proc: str, log_path: str) -> None:
    """Stop the launch when a worker found no TPU on a TPU host:
    retrying cannot help while another process holds the chip."""
    if rc == EXIT_NO_DEVICE:
        raise RuntimeError(
            f"{proc} found no TPU on a host that has one (another process "
            f"holds the chip); one worker per TPU host. "
            f"{_fail_report(workdir, proc, log_path)}")


def _backoff(base: float, attempt: int, rng: random.Random) -> float:
    """Exponential backoff with jitter: base * 2^(attempt-1) * U[1, 1.25]."""
    return base * (2.0 ** max(0, attempt - 1)) * (1.0 + 0.25 * rng.random())


# ---------------------------------------------------------------------------
# supervision loops
# ---------------------------------------------------------------------------
def _supervise_pinned(spec_path, workdir, spec, pending, env, *, n_workers,
                      retries, timeout, stall_timeout, backoff_base,
                      poll_interval, results, unexpected, attempts,
                      journal=None):
    """Shard-pinned supervision: one worker process per pending shard,
    polled concurrently against one shared deadline (no serial
    ``communicate(timeout)`` accounting), stale-heartbeat kills, retry
    budgets with exponential backoff + jitter."""
    jl = journal if journal is not None else Journal.noop()
    rng = random.Random(0xC0FFEE)
    t0 = time.monotonic()
    deadline = t0 + timeout
    pending = set(pending)
    next_spawn = {i: 0.0 for i in pending}
    procs, spawn_wall, last_log = {}, {}, {}
    try:
        while pending:
            now = time.monotonic()
            if now > deadline:
                raise RuntimeError(
                    f"sweep launch exceeded its shared deadline "
                    f"({timeout:.0f}s) with shards {sorted(pending)} "
                    f"unfinished")
            # spawn/respawn shards whose backoff has elapsed, bounded by
            # the worker-slot budget (n_shards may exceed n_workers)
            for i in sorted(pending - set(procs)):
                if len(procs) >= n_workers:
                    break
                if now < next_spawn[i]:
                    continue
                log = os.path.join(_worker_dir(workdir, i),
                                   f"log_{attempts[i]}.txt")
                last_log[i] = log
                procs[i] = _spawn([spec_path, str(i)], env, log)
                spawn_wall[i] = time.time()
                jl.event("spawn", "launcher", shard=i,
                         launch_attempt=attempts[i], pid_child=procs[i].pid)
            reaped = []
            for i, p in procs.items():
                rc = p.poll()
                if rc is None and stall_timeout:
                    # heartbeats are PROGRESS beats (touched at chunk
                    # boundaries), so a worker becomes stall-killable only
                    # once it has beaten during THIS attempt — startup
                    # (jax import + compile) must not read as a stall, and
                    # a stale file from the previous attempt must not kill
                    # a fresh worker. Process death is caught by poll();
                    # the shared deadline backstops a worker that wedges
                    # before its first boundary.
                    try:
                        beat = os.path.getmtime(_heartbeat_path(workdir, i))
                    except OSError:
                        beat = None
                    if (beat is not None and beat > spawn_wall[i]
                            and time.time() - beat > stall_timeout):
                        # stall diagnostics carry the heartbeat's step
                        # payload — WHERE the worker went quiet, not just
                        # how long ago
                        hb = read_heartbeat(_heartbeat_path(workdir, i))
                        hb_step = None if hb is None else hb.get("step")
                        age = time.time() - beat
                        print(f"launcher: shard {i} heartbeat {age:.1f}s "
                              f"stale (last step "
                              f"{'?' if hb_step is None else hb_step}) — "
                              f"killing wedged worker")
                        jl.event("stall_kill", "launcher", shard=i,
                                 beat_age_s=round(age, 3), step=hb_step)
                        p.kill()
                        p.wait()
                        rc = p.returncode
                if rc is None:
                    continue
                reaped.append(i)
                # a worker may die AFTER publishing (e.g. killed between
                # publish and cleanup) — the published result always wins,
                # so load regardless of the exit code
                res = _load_result(workdir, spec, i, unexpected)
                attempts[i] += 1
                if res is not None:
                    results[i] = res
                    pending.discard(i)
                    jl.event("shard_done", "launcher", shard=i,
                             launch_attempts=attempts[i], rc=rc)
                    continue
                _no_device(rc, workdir, f"worker_s{i}", last_log[i])
                if attempts[i] > retries:
                    raise RuntimeError(
                        f"sweep shard {i} failed after {retries + 1} "
                        f"attempts; "
                        f"{_fail_report(workdir, f'worker_s{i}', last_log[i])}")
                next_spawn[i] = now + _backoff(backoff_base, attempts[i],
                                               rng)
                jl.event("retry", "launcher", shard=i, rc=rc,
                         launch_attempt=attempts[i],
                         backoff_s=round(next_spawn[i] - now, 3))
            for i in reaped:
                procs.pop(i)
            if pending:
                time.sleep(poll_interval)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()


def _supervise_elastic(spec_path, workdir, spec, pending, env, *, n_workers,
                       retries, timeout, lease_ttl, backoff_base,
                       poll_interval, results, unexpected, attempts,
                       journal=None):
    """Elastic fleet supervision: ``n_workers`` un-pinned fleet workers
    lease-and-steal shards; the launcher only keeps worker SLOTS alive
    (respawning dead ones under a per-slot budget) and polls for published
    shard results. Extra workers may join from outside at any time; a
    worker leaving is just its leases expiring."""
    jl = journal if journal is not None else Journal.noop()
    rng = random.Random(0xE1A571C)
    deadline = time.monotonic() + timeout
    pending = set(pending)
    slot_attempts = {s: 0 for s in range(n_workers)}
    next_spawn = {s: 0.0 for s in range(n_workers)}
    procs, last_log = {}, {}
    try:
        while pending:
            now = time.monotonic()
            if now > deadline:
                raise RuntimeError(
                    f"elastic sweep launch exceeded its deadline "
                    f"({timeout:.0f}s) with shards {sorted(pending)} "
                    f"unfinished")
            for s in range(n_workers):
                p = procs.get(s)
                if p is not None:
                    if p.poll() is None:
                        continue
                    # a fleet worker exits 0 only once every shard is
                    # published; an exit with work still pending — clean or
                    # not — consumes this slot's retry budget
                    rc = p.returncode
                    procs.pop(s)
                    _no_device(rc, workdir, f"fleet_w{s}", last_log[s])
                    slot_attempts[s] += 1
                    jl.event("slot_exit", "launcher", slot=s, rc=rc,
                             slot_attempts=slot_attempts[s])
                    if slot_attempts[s] > retries:
                        continue  # slot exhausted; others may still finish
                    next_spawn[s] = now + _backoff(backoff_base,
                                                   slot_attempts[s], rng)
                    continue
                if now < next_spawn[s]:
                    continue
                log = os.path.join(workdir, f"fleet_w{s}",
                                   f"log_{slot_attempts[s]}.txt")
                last_log[s] = log
                procs[s] = _spawn(
                    [spec_path, "--fleet", "--worker", f"w{s}",
                     "--ttl", str(lease_ttl)], env, log)
                jl.event("spawn", "launcher", slot=s,
                         launch_attempt=slot_attempts[s],
                         pid_child=procs[s].pid)
            for i in sorted(pending):
                res = _load_result(workdir, spec, i, unexpected)
                if res is not None:
                    results[i] = res
                    attempts[i] += 1       # shard completed on some attempt
                    pending.discard(i)
            if pending:
                if not procs and all(a > retries
                                     for a in slot_attempts.values()):
                    tails = "\n".join(
                        _fail_report(workdir, f"fleet_w{s}", l)
                        for s, l in last_log.items())
                    raise RuntimeError(
                        f"all {n_workers} fleet worker slots exhausted "
                        f"their {retries + 1}-attempt budgets with shards "
                        f"{sorted(pending)} unfinished;\n{tails}")
                time.sleep(poll_interval)
    finally:
        # every shard is published (or we raised) — surviving fleet workers
        # are draining their own exit path; don't leave orphans behind
        for p in procs.values():
            if p.poll() is None:
                p.kill()


# ---------------------------------------------------------------------------
# launch
# ---------------------------------------------------------------------------
def launch_sweep(
    *,
    covs,
    cases: Sequence[dict],
    r: int,
    t_outer: int,
    t_c: int = 50,
    seeds: Sequence[int],
    q_true=None,
    workdir: str,
    n_workers: int = 2,
    n_shards: Optional[int] = None,
    retries: int = 1,
    timeout: float = 900.0,
    sweep_chunk: Optional[int] = None,
    elastic: bool = False,
    stall_timeout: Optional[float] = None,
    lease_ttl: float = 30.0,
    backoff_base: float = 0.5,
    poll_interval: float = 0.2,
    chaos_plan: Union[FaultPlan, dict, str, None] = None,
    net_faults: Union[dict, str, None] = None,
) -> SweepResult:
    """Shard a ``sdot_sweep`` case x seed grid over supervised workers.

    ``covs``: one (N, d, d) stack shared by every case, or a list with one
    stack per case (ragged node counts allowed — the workers run the same
    identity-padding path as single-process ``sdot_sweep``).  ``cases``:
    list of ``{"topology": {...}, "schedule": {...}}`` specs (see
    ``build_engine`` / ``build_schedule``).  The seed axis is split
    contiguously into ``n_shards`` lease-granular shards (default: one per
    worker), so the merged result preserves seed order and equals the
    single-process sweep exactly.

    Supervision (see module docstring): all workers are polled against ONE
    shared ``timeout`` deadline; a dead worker is respawned after
    exponential backoff with jitter under a ``retries`` budget; with
    ``sweep_chunk`` set, a worker whose heartbeat goes quiet for
    ``stall_timeout`` seconds (default 60; pass 0 to disable) is killed
    and retried. ``elastic=True`` runs un-pinned fleet workers that lease,
    steal, and resume shards (``lease_ttl`` controls when a silent shard
    becomes stealable) — workers can join or leave mid-sweep.

    ``sweep_chunk`` turns on MID-GRID fault tolerance: each worker runs its
    shard through the runtime's chunked driver, checkpointing the
    sweep-RunState into ``worker_<shard>/ckpt`` every ``sweep_chunk`` outer
    iterations — a killed (or robbed) worker resumes from the checkpoint
    (bitwise equal to the uninterrupted sweep) instead of recomputing its
    shard. The returned ``SweepResult.resume_report`` records reused
    shards, per-shard restored steps and attempt counts, stolen shards
    (elastic), and any unexpected restore errors.

    ``chaos_plan`` (a ``FaultPlan``, its dict form, or a path to one)
    injects seeded faults into the workers for robustness testing.

    ``net_faults`` (a net-fault document dict, or a path to one) makes
    every worker run its shard through ``core.netfaults.FaultyConsensus``
    — seeded link drops / bursty outages / crash-rejoin / payload
    corruption inside the gossip itself, with realized-mixing debias.
    Defaults from the ``REPRO_NET_FAULTS`` env var; the document enters
    the spec (and thus the fingerprint), so changing the fault model
    invalidates published shards just like changing the grid would.
    """
    os.makedirs(workdir, exist_ok=True)
    seeds = [int(s) for s in seeds]
    n_workers = max(1, min(int(n_workers), len(seeds)))
    if n_workers > 1 and tpu_expected():
        raise ValueError(
            f"n_workers={n_workers} on a host with {tpu_chips_on_host()} TPU "
            f"chip(s): a worker process opens every chip of its host, so a "
            f"second one would find none. Use n_workers=1 per TPU host (or "
            f"JAX_PLATFORMS=cpu for CPU workers).")
    shards = slice_seed_shards(seeds, n_shards if n_shards else n_workers)
    n_shards = len(shards)

    ragged = isinstance(covs, (list, tuple))
    if ragged and len(covs) not in (1, len(cases)):
        # enforce sdot_sweep's zip-broadcast contract before anything is
        # written, rather than as a KeyError inside every worker; a
        # 1-element list is written ONCE (not duplicated per case) and
        # broadcast worker-side by sdot_sweep itself
        raise ValueError(f"per-case covs must zip-broadcast with the "
                         f"cases: got {len(covs)} cov stacks for "
                         f"{len(cases)} cases")
    if net_faults is None:
        net_faults = net_faults_from_env()
    elif isinstance(net_faults, str):
        if net_faults.lstrip().startswith("{"):
            net_faults = json.loads(net_faults)
        else:
            with open(net_faults) as f:
                net_faults = json.load(f)
    if net_faults is not None:
        validate_net_fault_doc(net_faults)
        if ragged:
            # FaultyConsensus pre-samples (T, N, N) edge masks per case
            # lane; a ragged grid has no single N to sample against
            raise ValueError("net_faults requires a uniform node count "
                             "across cases (ragged per-case covs given)")
    if elastic and sweep_chunk is None:
        # stealing without checkpoints would recompute stolen shards from
        # scratch; default to chunked execution so a steal resumes mid-grid
        sweep_chunk = max(1, int(t_outer) // 5)
    spec = {
        "algo": "sdot",
        "r": int(r),
        "t_outer": int(t_outer),
        "t_c": int(t_c),
        "cases": list(cases),
        "shards": shards,
        "ragged": ragged,
        "n_cov_stacks": len(covs) if ragged else 1,
        "has_q_true": q_true is not None,
        "sweep_chunk": int(sweep_chunk) if sweep_chunk else None,
    }
    if net_faults is not None:
        # inside the spec -> inside spec_fingerprint: a changed fault
        # model invalidates published shards and intermediate checkpoints
        spec["net_faults"] = net_faults
    spec_path = os.path.join(workdir, _SPEC)
    with open(spec_path, "w") as f:
        json.dump(spec, f, indent=2)

    # a changed spec invalidates the workers' intermediate sweep
    # checkpoints (published results carry their own fingerprint stamp;
    # the ckpt dirs don't, so they are guarded here at the workdir level)
    fp = str(spec_fingerprint(spec))
    fp_path = os.path.join(workdir, "spec_fp")
    if os.path.exists(fp_path):
        with open(fp_path) as f:
            if f.read().strip() != fp:
                for name in os.listdir(workdir):
                    ckpt = os.path.join(workdir, name, "ckpt")
                    if name.startswith("worker_") and os.path.isdir(ckpt):
                        shutil.rmtree(ckpt, ignore_errors=True)
                shutil.rmtree(os.path.join(workdir, "leases"),
                              ignore_errors=True)
    with open(fp_path, "w") as f:
        f.write(fp)

    arrays = {}
    if ragged:
        for ci, c in enumerate(covs):
            arrays[f"covs_{ci}"] = np.asarray(c)
    else:
        arrays["covs"] = np.asarray(covs)
    if q_true is not None:
        arrays["q_true"] = np.asarray(q_true)
    np.savez(os.path.join(workdir, _PROBLEM), **arrays)

    env = dict(os.environ)
    src = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    if chaos_plan is not None:
        if isinstance(chaos_plan, dict):
            chaos_plan = FaultPlan(chaos_plan.get("faults", []),
                                   seed=chaos_plan.get("seed", 0))
        if hasattr(chaos_plan, "dump"):   # FaultPlan, possibly the
            # __main__-module twin when chaos.py runs as a script
            chaos_plan = chaos_plan.dump(os.path.join(workdir, _CHAOS_PLAN))
        env[ENV_PLAN] = str(chaos_plan)
    else:
        env.pop(ENV_PLAN, None)

    if stall_timeout is None:
        stall_timeout = 60.0 if sweep_chunk else 0.0

    # published shards are reused only if their stamped spec fingerprint
    # matches; stale/corrupt ones are cleared and recomputed
    unexpected: dict = {}
    results = {i: _load_result(workdir, spec, i, unexpected)
               for i in range(n_shards)}
    pending = [i for i, t in results.items() if t is None]
    reused = sorted(i for i, t in results.items() if t is not None)
    for i in pending:
        shutil.rmtree(_result_dir(workdir, i), ignore_errors=True)
    attempts = {i: 0 for i in range(n_shards)}
    if pending:
        # the launcher keeps its OWN journal (not the process-global one:
        # launch_sweep is a library call — tests and services drive it from
        # processes whose journal belongs to them)
        obs_dir = obs_dir_for(workdir)
        jl = (Journal.open(obs_dir, "launcher") if obs_dir is not None
              else Journal.noop())
        supervise = _supervise_elastic if elastic else _supervise_pinned
        kw = ({"lease_ttl": lease_ttl} if elastic
              else {"stall_timeout": stall_timeout})
        try:
            with jl.span("supervise", "launcher", n_shards=n_shards,
                         n_workers=n_workers, elastic=elastic,
                         pending=sorted(pending),
                         chaos=chaos_plan is not None):
                supervise(spec_path, workdir, spec, pending, env,
                          n_workers=n_workers, retries=retries,
                          timeout=timeout, backoff_base=backoff_base,
                          poll_interval=poll_interval, results=results,
                          unexpected=unexpected, attempts=attempts,
                          journal=jl, **kw)
        finally:
            jl.close()

    # gather + merge along the seed axis (shards are contiguous slices)
    trees = [results[i] for i in range(n_shards)]
    resumed_steps = {i: int(t["resumed_steps"]) for i, t in enumerate(trees)}
    report = {
        # shards whose published result was reused wholesale — their whole
        # case x seed sub-grid was skipped
        "reused_shards": reused,
        "skipped_grid_points": sum(len(shards[i]) for i in reused)
        * len(cases),
        # outer step each shard's restored sweep-RunState already carried
        # (0 = computed from scratch)
        "worker_resumed_steps": resumed_steps,
        # attempts this launch spent per shard (0 = reused, 1 = first try)
        "attempts": attempts,
    }
    if unexpected:
        report["load_errors"] = dict(unexpected)
    if elastic:
        leases = LeaseStore(workdir, ttl=lease_ttl).snapshot()
        report["lease_owners"] = {s: l.owners for s, l in leases.items()}
        report["stolen_shards"] = sorted(
            s for s, l in leases.items() if len(set(l.owners)) > 1)
    return SweepResult.merge_shards(
        trees, n_cases=len(cases), has_err=spec["has_q_true"],
        ragged=spec["ragged"], resume_report=report)
