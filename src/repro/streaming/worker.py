"""Sweep worker: one shard of a sharded Monte-Carlo sweep, fleet-hardened.

Usage (spawned by ``streaming/launcher.py``; runnable by hand for debugs):

    python -m repro.streaming.worker <workdir>/spec.json <shard_idx>
    python -m repro.streaming.worker <workdir>/spec.json --fleet \
        --worker w0 [--ttl 30]

The pinned form runs exactly one shard. The ``--fleet`` form runs the
elastic loop (``streaming/fleet.py``): acquire any available shard lease,
run it — resuming from whatever sweep-RunState checkpoint the previous
owner published — release, steal the next, and exit once every shard has a
published result. New fleet workers can join a sweep at any time; leaving
is just letting the lease expire.

Rebuilds its engines/schedules from the spec (seed-deterministic graph
constructions — no pickled objects cross the host boundary), loads the cov
stacks from ``problem.npz``, runs ``sdot_sweep`` over its shard's seed
slice, and publishes ``{q, error_traces, seeds, ledger}`` atomically into
``<workdir>/worker_<shard>/result``. If a valid result is already
published the worker exits immediately (idempotent relaunch) — and also
sweeps away any leftover ``ckpt`` dir, closing the crash window between
result publish and checkpoint cleanup: the published result ALWAYS wins
over a stale intermediate checkpoint.

Robustness wiring (all no-ops outside a supervised launch):

* a **heartbeat** file ``worker_<shard>/heartbeat`` is touched at every
  chunk boundary (via ``CheckpointManager.on_save``) and just before the
  result publish, so the launcher's supervision loop can spot a wedged
  worker by staleness (it is a PROGRESS beat: the launcher only treats a
  worker as stalled once it has beaten at least once this attempt, so
  import/compile startup never reads as a stall);
* the worker turns on the compile cache and exits with
  ``launch.device.EXIT_NO_DEVICE`` when the host has a TPU it could not
  open, instead of running on the CPU;
* **chaos hooks** (``streaming/chaos.py``) are installed from the
  ``REPRO_CHAOS_PLAN`` env var — production code carries no fault-injection
  branches;
* under a **lease** (fleet mode) every chunk boundary renews the lease;
  a foreign fencing token raises ``LeaseLost`` and the shard is abandoned
  mid-run instead of wasting compute on stolen work.

With ``spec["sweep_chunk"]`` set, the shard runs through the unified
runtime's CHUNKED driver: the sweep-RunState (case x seed lane axes riding
on every buffer) checkpoints into ``<workdir>/worker_<shard>/ckpt`` every
``sweep_chunk`` outer iterations, so a killed worker — or a stealing
neighbour — resumes MID-GRID, bitwise equal to the uninterrupted sweep.
The published result records ``resumed_steps`` for the launcher's resume
report.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys


def run_shard(spec: dict, workdir: str, shard: int, *, worker=None,
              lease_store=None, lease=None) -> int:
    """Compute and publish one shard (idempotent; resumes from checkpoints).

    ``worker`` is the process identity for chaos targeting and lease
    ownership (defaults to the shard index). ``lease_store``/``lease``
    wire per-chunk-boundary lease renewal in fleet mode."""
    from repro.obs import get_journal
    from repro.streaming.launcher import _load_result, _worker_dir

    shard = int(shard)
    shard_dir = _worker_dir(workdir, shard)
    out_dir = os.path.join(shard_dir, "result")
    ckpt_dir = os.path.join(shard_dir, "ckpt")
    worker_id = str(worker) if worker is not None else str(shard)

    # idempotent relaunch — but only for a result stamped with THIS spec's
    # fingerprint: a hand-run worker in a reused workdir must not keep a
    # shard computed under an older spec. The published result always wins;
    # any ckpt dir a crash left behind next to it is stale by definition
    # and is cleaned up here, making the publish->cleanup pair idempotent.
    if _load_result(workdir, spec, shard) is not None:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        get_journal().event("shard_skip", "worker", shard=shard)
        print(f"worker {shard}: result already published, nothing to do")
        return 0
    shutil.rmtree(out_dir, ignore_errors=True)

    # the whole shard is ONE span: a chaos kill (or LeaseLost abandon)
    # leaves it open in the journal, which is how forensics names the work
    # a dead/robbed worker was doing
    sp = get_journal().begin("shard_run", "worker", shard=shard,
                             worker=worker_id)

    try:
        return _run_shard_body(spec, workdir, shard, worker_id, sp,
                               lease_store, lease)
    except BaseException:
        # close the span for survivable aborts (LeaseLost, raised errors) —
        # a SIGKILL never reaches here and leaves the span_start orphaned,
        # by design
        sp.end(ok=False)
        raise


def _run_shard_body(spec, workdir, shard, worker_id, sp, lease_store,
                    lease) -> int:
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.manager import CheckpointManager, save_tree
    from repro.core.sweep import netfault_sweep, sdot_sweep
    from repro.obs import get_journal
    from repro.streaming import chaos
    from repro.streaming.fleet import touch_heartbeat
    from repro.streaming.launcher import (_worker_dir, build_engine,
                                          build_schedule, spec_fingerprint)

    shard_dir = _worker_dir(workdir, shard)
    out_dir = os.path.join(shard_dir, "result")
    ckpt_dir = os.path.join(shard_dir, "ckpt")
    hb_path = os.path.join(shard_dir, "heartbeat")

    seeds = spec["shards"][shard]
    if not seeds:
        raise ValueError(f"worker {shard} got an empty seed shard")
    problem = np.load(os.path.join(workdir, "problem.npz"))
    engines = [build_engine(c["topology"]) for c in spec["cases"]]
    schedules = [build_schedule(c.get("schedule"), spec["t_outer"],
                                spec["t_c"]) for c in spec["cases"]]
    if spec["ragged"]:
        # a 1-element list is stored once; sdot_sweep zip-broadcasts it
        covs = [jnp.asarray(problem[f"covs_{ci}"])
                for ci in range(spec["n_cov_stacks"])]
    else:
        covs = jnp.asarray(problem["covs"])
    q_true = (jnp.asarray(problem["q_true"]) if spec["has_q_true"]
              else None)

    sweep_chunk = spec.get("sweep_chunk")
    n_boundaries = (-(-spec["t_outer"] // sweep_chunk) if sweep_chunk else 1)
    hooks = chaos.hooks_from_env(shard=shard, worker=worker_id,
                                 n_boundaries=n_boundaries,
                                 ckpt_root=ckpt_dir, workdir=workdir)

    def on_boundary(step: int) -> None:
        # chunk-boundary side effects, in supervision order: inject faults
        # first (a killed worker must not beat), then beat, then renew the
        # lease (a stolen lease aborts the run via LeaseLost)
        hooks.at_boundary(step)
        touch_heartbeat(hb_path, step=step)
        if lease_store is not None and lease is not None:
            lease_store.renew(shard, worker_id, lease.token)

    manager = None
    if sweep_chunk:
        # chunked-resumable shard: the sweep-RunState checkpoints at every
        # chunk boundary, and a restarted (or stealing) worker continues
        # mid-grid from it
        manager = CheckpointManager(ckpt_dir, on_save=on_boundary)

    if spec.get("net_faults"):
        # gossip-layer fault injection: wrap every case engine in a
        # FaultyConsensus built from the spec's net-fault document — the
        # document is part of the spec fingerprint, so every worker (and
        # every resume) runs the identical seeded fault realization
        from repro.core.netfaults import FaultyConsensus
        model, fseed, debias = chaos.net_fault_model_from_dict(
            spec["net_faults"])
        engines = [FaultyConsensus(graph=e.graph, faults=model, seed=fseed,
                                   debias=debias) for e in engines]
        sw = netfault_sweep(covs=covs, engines=engines,
                            schedules=schedules, r=spec["r"],
                            t_outer=spec["t_outer"], t_c=spec["t_c"],
                            seeds=seeds, q_true=q_true,
                            manager=manager, chunk_size=sweep_chunk)
    else:
        sw = sdot_sweep(covs=covs, engines=engines, schedules=schedules,
                        r=spec["r"], t_outer=spec["t_outer"],
                        t_c=spec["t_c"], seeds=seeds, q_true=q_true,
                        manager=manager, chunk_size=sweep_chunk)
    # the step the runtime ACTUALLY restored (a corrupt/stale newest
    # checkpoint falls back, so this can be less than the dir's latest step)
    resumed_steps = sw.resumed_step

    # the stamped fingerprint lets the launcher reject this result if the
    # workdir is later reused with a different spec
    tree = {"q": sw.q, "seeds": jnp.asarray(np.asarray(seeds)),
            "ledger": sw.ledger,
            "resumed_steps": jnp.asarray(resumed_steps, jnp.int32),
            "spec_fp": jnp.asarray(spec_fingerprint(spec), jnp.int32)}
    if spec["has_q_true"]:
        tree["error_traces"] = jnp.asarray(sw.error_traces)
    if spec["ragged"]:
        tree["node_counts"] = jnp.asarray(sw.node_counts)
    touch_heartbeat(hb_path, step=spec["t_outer"])
    save_tree(out_dir, tree, step=shard)
    get_journal().event("publish", "worker", shard=shard,
                        n_seeds=len(seeds),
                        resumed_steps=int(resumed_steps))
    hooks.after_publish(out_dir)
    # the published result supersedes the intermediate sweep state; a kill
    # landing between the publish above and this cleanup is benign — the
    # relaunch path at the top of this function redoes the cleanup and the
    # result always wins over the stale checkpoint
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    sp.end(n_seeds=len(seeds), resumed_steps=int(resumed_steps))
    print(f"worker {shard}: published {len(seeds)} seed lanes -> {out_dir}"
          + (f" (resumed from outer step {resumed_steps})"
             if resumed_steps else ""))
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("spec", help="path to <workdir>/spec.json")
    ap.add_argument("shard", nargs="?", default=None,
                    help="shard index (pinned mode)")
    ap.add_argument("--fleet", action="store_true",
                    help="elastic mode: lease-and-steal shards until the "
                         "whole grid is published")
    ap.add_argument("--worker", default=None,
                    help="fleet worker identity (e.g. w0)")
    ap.add_argument("--ttl", type=float, default=30.0,
                    help="lease time-to-live in seconds (fleet mode)")
    args = ap.parse_args(argv)
    if args.fleet == (args.shard is not None):
        ap.error("pass a shard index (pinned) or --fleet (elastic), not both")

    workdir = os.path.dirname(os.path.abspath(args.spec))
    with open(args.spec) as f:
        spec = json.load(f)

    from repro.launch.device import (EXIT_NO_DEVICE, init_compile_cache,
                                     require_expected_platform)
    from repro.obs import install

    init_compile_cache()
    try:
        require_expected_platform()
    except RuntimeError as e:
        print(f"worker: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE

    if args.fleet:
        from repro.streaming.fleet import fleet_worker_loop
        worker_id = args.worker or f"w{os.getpid()}"
        # attempt-scoped journal: a respawned slot opens fleet_w0.a1.jsonl
        # next to the crashed attempt's fleet_w0.a0.jsonl
        install(workdir, f"fleet_{worker_id}")
        return fleet_worker_loop(spec, workdir, worker_id, ttl=args.ttl)
    install(workdir, f"worker_s{int(args.shard)}")
    return run_shard(spec, workdir, int(args.shard), worker=args.worker)


if __name__ == "__main__":
    sys.exit(main())
