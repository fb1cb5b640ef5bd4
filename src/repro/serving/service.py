"""Always-fresh subspace serving: the crash-resumable PSA service loop.

The paper solves ONE principal-subspace problem; a deployment serves the
subspace of a stream whose population changes under it. ``PSAService``
closes that loop as a sequence of deterministic *ticks*:

    ingest -> drift detect -> (warm re-solve, a few chunks) -> quality gate
           -> atomic swap -> answer queries -> checkpoint

* **Ingest** — one micro-batch per tick into a ``StreamingIngestor``
  (``track_top=r``), whose tracked Ritz spectrum feeds the drift detector.
* **Drift -> warm re-solve** — when ``drift.DriftDetector`` triggers, the
  service freezes the current cov stack and starts an S-DOT re-solve
  **warm-started from the currently-served iterate**, driven through
  ``core.runtime.run_chunked(..., target_step=...)`` a few chunks per tick:
  the re-solve's RunState lives in its own checkpoint directory, so a kill
  at any chunk boundary resumes bit-identically, and because the per-tick
  target is an ABSOLUTE step, re-executing a crashed tick never
  double-advances the solve. The incumbent subspace keeps answering
  queries the whole time — staleness is a surfaced metric, never a stall.
* **Quality gate -> atomic swap** — a finished candidate must be finite,
  orthonormal, and explain at least as much variance as the incumbent on a
  *held-out* sample batch (fresh draws from the same population, keyed by
  the current stream step). Pass: the swap is atomic (one reference
  assignment; queries batch against one Q at a time) and the tick's
  service snapshot is **pinned** in the checkpoint manager so retention
  churn can never GC the last-good served subspace. Fail (NaN/diverged/
  chaos-mangled): the candidate is *never served* — the incumbent stays,
  the reject is counted, and a cold re-solve starts from a fresh seed.
* **Queries** — ``query.QueryPath``: bounded admission, per-request
  deadlines, explicit shedding, p50/p99 accounting.
* **Checkpoint** — the whole service state (ingest sketches + Ritz track,
  served subspace, re-solve bookkeeping, counters) is ONE fixed-structure
  pytree saved at every tick boundary. Every tick is a pure function of
  the restored state (streams are stateless-seeded, the re-solve target is
  absolute), so a SIGKILL anywhere re-executes at most one tick and the
  served-subspace trajectory — swap ticks and served bits — is IDENTICAL
  to the uninterrupted run's.

``run_supervised`` wraps the loop in the fleet's supervision idiom:
subprocess + heartbeat-staleness watchdog + relaunch with backoff. The
supervisor itself never opens a JAX backend, so the chip is free for its
child.
``run_smoke`` is the CI scenario: the same config run (a) fault-free,
(b) under a kill/kill/hang FaultPlan with supervision, asserting the
served trajectory is bit-identical and every restore matched the pinned
last-good snapshot, and (c) under a corrupt-candidate + delay-query plan,
asserting the gate rejected the mangled candidate, a cold re-solve
recovered, and delayed queries expired instead of blocking.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.manager import CheckpointManager
from ..core.linalg import cholesky_qr2, orthonormal_init
from ..core.runtime import run_chunked
from ..core.sdot import sdot_program
from ..data.pipeline import drifting_eigengap_stream
from ..launch.device import (EXIT_NO_DEVICE, init_compile_cache,
                             require_expected_platform)
from ..obs import install as obs_install
from ..obs import metrics as obs_metrics
from ..obs import obs_dir_for
from ..streaming.chaos import ENV_PLAN, ChaosHooks, FaultPlan
from ..streaming.ingest import StreamingIngestor
from ..streaming.launcher import build_engine
from .drift import DriftDetector
from .query import QueryPath

__all__ = ["ServiceConfig", "PSAService", "run_supervised", "run_smoke",
           "service_summary"]

_STATE = "state"          # <workdir>/state: per-tick service snapshots
_RESOLVE = "resolve"      # <workdir>/resolve: active re-solve RunState
_EVENTS = "events.jsonl"
_FINAL = "final.json"
_HEARTBEAT = "heartbeat"


@dataclasses.dataclass
class ServiceConfig:
    """Everything a service run needs, JSON-round-trippable for the
    supervisor's subprocess handoff. The drifting stream is part of the
    config (not an injected callable) so a relaunched process rebuilds the
    *identical* pure (seed, step) stream."""

    d: int = 12
    r: int = 3
    n_nodes: int = 4
    batch_size: int = 32
    # drifting stream: population C0 (lead) until stream step shift_at,
    # then an independently rotated C1 (shift_lead) — shift_lead > lead
    # makes the post-shift directions dominate the blended sketch quickly
    gap: float = 0.6
    lead: float = 3.0
    shift_lead: float = 6.0
    shift_at: int = 8
    stream_seed: int = 0
    # held-out gate mass: fresh draws from the same population at the
    # current stream step (never fed to the ingestor)
    holdout_seed: int = 777
    holdout_m: int = 512
    total_ticks: int = 26
    # re-solve: t_outer S-DOT iterations advanced resolve_chunk *
    # chunks_per_tick steps per service tick through run_chunked
    t_outer: int = 12
    t_c: int = 12
    resolve_chunk: int = 3
    chunks_per_tick: int = 1
    topology: dict = dataclasses.field(default_factory=lambda: {
        "kind": "er", "n": 4, "p": 0.6, "seed": 1})
    warmup_ticks: int = 2          # ticks before the initial cold solve
    drift_threshold: float = 0.25  # residual trigger (above sampling noise)
    drift_warmup: int = 3          # post-swap ticks with no trigger
    # query path
    queries_per_tick: int = 8
    queue_capacity: int = 32
    max_batch: int = 8
    deadline_s: float = 0.25
    query_mode: str = "project"
    staleness_bound: int = 20      # asserted ceiling on served staleness
    keep_last: int = 4
    seed: int = 0

    def to_json(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(dataclasses.asdict(self), f, indent=2)
        return path

    @classmethod
    def from_json(cls, path: str) -> "ServiceConfig":
        with open(path) as f:
            return cls(**json.load(f))


def _touch(path: str) -> None:
    with open(path, "w") as f:
        f.write(str(time.time()))


class PSAService:
    """The tick loop (see module docstring). One instance == one process
    attempt; construct + ``run()`` resumes from the newest restorable
    service snapshot in ``workdir`` or starts fresh."""

    def __init__(self, cfg: ServiceConfig, workdir: str,
                 plan: Optional[FaultPlan] = None):
        self.cfg = cfg
        self.workdir = workdir
        os.makedirs(workdir, exist_ok=True)
        # process journal + metrics registry for this service attempt; the
        # runtime/checkpoint/chaos seams pick the journal up via
        # get_journal(), and the query path shares the registry so its
        # latency histogram lands in the finalize dump
        self.journal = obs_install(workdir, "service")
        self.registry = obs_metrics()
        state_root = os.path.join(workdir, _STATE)
        self.resolve_root = os.path.join(workdir, _RESOLVE)
        chaos_dir = os.path.join(workdir, "chaos_state")
        # two hook instances over ONE plan: faults target the service tick
        # boundary (worker "service") or the re-solve chunk boundary
        # (worker "resolve"); both anchor to absolute step numbers so a
        # plan reads the same before and after a crash
        self.hooks = ChaosHooks(plan, worker="service",
                                n_boundaries=cfg.total_ticks,
                                ckpt_root=state_root, state_dir=chaos_dir,
                                step_boundaries=True)
        self.resolve_hooks = ChaosHooks(plan, worker="resolve",
                                        n_boundaries=cfg.t_outer,
                                        ckpt_root=self.resolve_root,
                                        state_dir=chaos_dir,
                                        step_boundaries=True)
        self.state_mgr = CheckpointManager(
            state_root, keep_last=cfg.keep_last, on_save=self._on_tick_save)

        # pure (seed, step) drifting stream — a relaunch rebuilds the
        # identical stream, shift included
        batch_fn, (c0, _), (c1, self.q_post) = drifting_eigengap_stream(
            cfg.d, cfg.r, cfg.gap, cfg.shift_at, seed=cfg.stream_seed,
            lead=cfg.lead, shift_lead=cfg.shift_lead)
        self._hold_chol = (
            np.linalg.cholesky(np.asarray(c0, np.float64)
                               + 1e-12 * np.eye(cfg.d)),
            np.linalg.cholesky(np.asarray(c1, np.float64)
                               + 1e-12 * np.eye(cfg.d)))
        self.ingestor = StreamingIngestor(
            n_nodes=cfg.n_nodes, d=cfg.d, batch_fn=batch_fn,
            batch_size=cfg.batch_size, track_top=cfg.r, ritz_seed=cfg.seed)
        self.engine = build_engine(cfg.topology)
        self.detector = DriftDetector(residual_threshold=cfg.drift_threshold,
                                      warmup=cfg.drift_warmup)
        self.queries = QueryPath(capacity=cfg.queue_capacity,
                                 max_batch=cfg.max_batch,
                                 deadline_s=cfg.deadline_s,
                                 mode=cfg.query_mode, hooks=self.hooks,
                                 registry=self.registry)
        self.queries.warmup(cfg.d, cfg.r)
        self.history: list = []      # per-tick metrics (host-only)

        # -- mutable service state (the checkpointed tree) ------------------
        self.tick = -1                           # last COMPLETED tick
        self.served_q = np.asarray(orthonormal_init(
            jax.random.PRNGKey(cfg.seed), cfg.d, cfg.r), np.float32)
        self.served_at = -1                      # tick of last swap
        self.served_stream_step = 0              # freeze step of served Q
        self.swaps = 0
        self.gate_rejects = 0
        self.cold_resolves = 0                   # gate-fallback cold starts
        self.max_staleness = 0
        self.baseline_gap = 0.0
        self.resolve_active = False
        self.resolve_cold = True
        self.resolve_id = -1                     # id of the ACTIVE resolve
        self.resolve_done = 0                    # absolute steps completed
        self.resolve_frozen_step = 0
        self.resolve_covs = np.zeros((cfg.n_nodes, cfg.d, cfg.d), np.float32)
        self.resolve_qinit = np.zeros((cfg.d, cfg.r), np.float32)
        self._restore()

    # -- checkpointing ------------------------------------------------------
    def _tree(self) -> dict:
        return {
            "tick": jnp.int32(self.tick),
            "served_q": jnp.asarray(self.served_q),
            "served_at": jnp.int32(self.served_at),
            "served_stream_step": jnp.int32(self.served_stream_step),
            "swaps": jnp.int32(self.swaps),
            "gate_rejects": jnp.int32(self.gate_rejects),
            "cold_resolves": jnp.int32(self.cold_resolves),
            "max_staleness": jnp.int32(self.max_staleness),
            "baseline_gap": jnp.float32(self.baseline_gap),
            "resolve": {
                "active": jnp.int32(self.resolve_active),
                "cold": jnp.int32(self.resolve_cold),
                "id": jnp.int32(self.resolve_id),
                "done": jnp.int32(self.resolve_done),
                "frozen_step": jnp.int32(self.resolve_frozen_step),
                "covs": jnp.asarray(self.resolve_covs),
                "qinit": jnp.asarray(self.resolve_qinit),
            },
            "ingest": self.ingestor.state(),
        }

    def _adopt(self, tree: dict) -> None:
        self.tick = int(tree["tick"])
        self.served_q = np.asarray(tree["served_q"], np.float32)
        self.served_at = int(tree["served_at"])
        self.served_stream_step = int(tree["served_stream_step"])
        self.swaps = int(tree["swaps"])
        self.gate_rejects = int(tree["gate_rejects"])
        self.cold_resolves = int(tree["cold_resolves"])
        self.max_staleness = int(tree["max_staleness"])
        self.baseline_gap = float(tree["baseline_gap"])
        res = tree["resolve"]
        self.resolve_active = bool(int(res["active"]))
        self.resolve_cold = bool(int(res["cold"]))
        self.resolve_id = int(res["id"])
        self.resolve_done = int(res["done"])
        self.resolve_frozen_step = int(res["frozen_step"])
        self.resolve_covs = np.asarray(res["covs"], np.float32)
        self.resolve_qinit = np.asarray(res["qinit"], np.float32)
        self.ingestor.restore(tree["ingest"])

    def _restore(self) -> None:
        """Adopt the newest restorable snapshot (corrupt steps skipped) and
        record whether the restored served subspace matches the pinned
        last-good one bitwise — the serving twin of runtime._restore_any."""
        template = self._tree()
        steps = self.state_mgr.all_steps()
        for step in reversed(steps):
            try:
                tree, _ = self.state_mgr.restore(template, step=step)
            except Exception:
                continue
            self._adopt(tree)
            pinned = self.state_mgr.pinned_steps()
            match = None
            if pinned:
                try:
                    ptree, _ = self.state_mgr.restore(template,
                                                      step=pinned[-1])
                    match = bool(np.array_equal(
                        np.asarray(ptree["served_q"], np.float32),
                        self.served_q))
                except Exception:
                    match = False
            self._event({"type": "restore", "tick": self.tick,
                         "from_step": step, "pinned_match": match})
            return

    def _on_tick_save(self, step: int) -> None:
        # beat BEFORE chaos: a hang fault must leave a stale (not fresh)
        # heartbeat for the supervisor's watchdog to see
        _touch(os.path.join(self.workdir, _HEARTBEAT))
        self.hooks.at_boundary(step)

    def _on_resolve_save(self, step: int) -> None:
        _touch(os.path.join(self.workdir, _HEARTBEAT))
        self.resolve_hooks.at_boundary(step)

    def _event(self, doc: dict) -> None:
        # append-only across restarts; a re-executed tick appends an
        # identical duplicate, which summarization dedups keep-first
        with open(os.path.join(self.workdir, _EVENTS), "a") as f:
            f.write(json.dumps(doc) + "\n")

    # -- held-out quality gate ----------------------------------------------
    def _holdout_cov(self) -> np.ndarray:
        """Fresh (d, d) sample covariance from the CURRENT population —
        independent draws the ingestor never saw, keyed by the stream step
        so the gate is a pure function of service state."""
        cfg = self.cfg
        step = self.ingestor.step
        chol = self._hold_chol[0 if step < cfg.shift_at else 1]
        rng = np.random.default_rng(cfg.holdout_seed * 9973 + step)
        x = chol @ rng.standard_normal((cfg.d, cfg.holdout_m))
        return (x @ x.T / cfg.holdout_m).astype(np.float32)

    def _gate(self, candidate: np.ndarray) -> tuple:
        """(accept, reason, cand_ev, inc_ev): candidate must be finite,
        orthonormal, and explain >= the incumbent's variance on held-out
        mass (small relative slack so a statistically-equal candidate from
        a fresher freeze still lands)."""
        if not np.all(np.isfinite(candidate)):
            return False, "nonfinite", float("nan"), float("nan")
        gram = candidate.T @ candidate
        ortho = float(np.linalg.norm(gram - np.eye(self.cfg.r)))
        if ortho > 1e-2:
            return False, f"nonorthonormal({ortho:.2e})", float("nan"), \
                float("nan")
        c_hold = self._holdout_cov()
        cand_ev = float(np.trace(candidate.T @ c_hold @ candidate))
        inc_ev = float(np.trace(self.served_q.T @ c_hold @ self.served_q))
        if cand_ev < inc_ev * (1.0 - 1e-3):
            return False, "worse_than_incumbent", cand_ev, inc_ev
        return True, "ok", cand_ev, inc_ev

    # -- re-solve lifecycle -------------------------------------------------
    def _start_resolve(self, *, cold: bool) -> None:
        cfg = self.cfg
        self.resolve_id += 1
        self.resolve_active = True
        self.resolve_cold = cold
        self.resolve_done = 0
        self.resolve_frozen_step = self.ingestor.step
        self.resolve_covs = np.asarray(self.ingestor.cov_stack(), np.float32)
        if cold:
            self.resolve_qinit = np.asarray(orthonormal_init(
                jax.random.PRNGKey(cfg.seed * 7 + 100 + self.resolve_id),
                cfg.d, cfg.r), np.float32)
        else:
            self.resolve_qinit = self.served_q.copy()
        shutil.rmtree(self.resolve_root, ignore_errors=True)
        self._event({"type": "start", "tick": self.tick + 1,
                     "resolve_id": self.resolve_id, "cold": cold,
                     "frozen_step": self.resolve_frozen_step})
        self.journal.event("resolve_start", "resolve",
                           tick=self.tick + 1, resolve_id=self.resolve_id,
                           cold=cold, frozen_step=self.resolve_frozen_step)

    def _advance_resolve(self) -> None:
        """A few chunks of the active re-solve, to an ABSOLUTE target step —
        a crashed tick's re-execution restores the re-solve RunState at (or
        past) the same target and can never double-advance it."""
        cfg = self.cfg
        target = min(self.resolve_done + cfg.resolve_chunk
                     * cfg.chunks_per_tick, cfg.t_outer)
        mgr = CheckpointManager(self.resolve_root, keep_last=3,
                                on_save=self._on_resolve_save)
        program = sdot_program(
            covs=jnp.asarray(self.resolve_covs), engine=self.engine,
            r=cfg.r, t_outer=cfg.t_outer, t_c=cfg.t_c,
            q_init=jnp.asarray(self.resolve_qinit))
        with self.journal.span("resolve_increment", "resolve",
                               tick=self.tick + 1,
                               resolve_id=self.resolve_id,
                               target=target, cold=self.resolve_cold):
            result = run_chunked(program, mgr, chunk_size=cfg.resolve_chunk,
                                 target_step=target)
        self.resolve_done = target
        if target < cfg.t_outer:
            return
        # complete: consensus-average the node iterates, re-orthonormalize,
        # hand the candidate to chaos (the gate's adversary), then gate it
        candidate = np.asarray(
            cholesky_qr2(result.q_nodes.mean(axis=0))[0], np.float32)
        candidate = np.asarray(self.hooks.mangle_candidate(
            candidate, self.resolve_id), np.float32)
        gate_sp = self.journal.begin("gate", "resolve",
                                     tick=self.tick + 1,
                                     resolve_id=self.resolve_id)
        accept, reason, cand_ev, inc_ev = self._gate(candidate)
        gate_sp.end(accept=accept, reason=reason)
        if accept:
            # the atomic swap: one assignment; queries only ever batch
            # against a fully-published Q
            self.served_q = candidate
            self.served_at = self.tick + 1
            self.served_stream_step = self.resolve_frozen_step
            self.swaps += 1
            self.baseline_gap = self.ingestor.eigengap
            self.resolve_active = False
            self._event({"type": "swap", "tick": self.tick + 1,
                         "resolve_id": self.resolve_id,
                         "cold": self.resolve_cold,
                         "cand_ev": round(cand_ev, 6),
                         "inc_ev": round(inc_ev, 6),
                         "frozen_step": self.resolve_frozen_step})
            self.journal.event("swap", "resolve", tick=self.tick + 1,
                               resolve_id=self.resolve_id,
                               frozen_step=self.resolve_frozen_step)
            self.registry.counter("serving_swaps_total").inc()
        else:
            # never served: incumbent stays, cold re-solve from fresh seed
            self.gate_rejects += 1
            self.cold_resolves += 1
            self._event({"type": "reject", "tick": self.tick + 1,
                         "resolve_id": self.resolve_id, "reason": reason,
                         "cand_ev": cand_ev, "inc_ev": inc_ev})
            self.journal.event("reject", "resolve", tick=self.tick + 1,
                               resolve_id=self.resolve_id, reason=reason)
            self.registry.counter("serving_gate_rejects_total").inc()
            self._start_resolve(cold=True)

    # -- the tick -----------------------------------------------------------
    def _run_tick(self) -> None:
        cfg = self.cfg
        tick = self.tick + 1
        jl = self.journal
        # one span per tick; a chaos kill mid-tick leaves it (and the phase
        # span it died inside) orphaned — that pair IS the forensics answer
        # to "what was the service doing when it died"
        tick_sp = jl.begin("tick", "serving", tick=tick)

        # 1) ingest this tick's micro-batch (pure in (seed, step))
        with jl.span("ingest", "serving", tick=tick):
            self.ingestor.ingest(1)

        # 2) re-solve lifecycle: advance the active one, or decide to start
        if self.resolve_active:
            self._advance_resolve()
        elif self.swaps == 0:
            if tick >= cfg.warmup_ticks:
                self._start_resolve(cold=True)
                self._advance_resolve()
        else:
            with jl.span("drift_read", "serving", tick=tick) as dsp:
                stats = self.detector.read(
                    self.ingestor, jnp.asarray(self.served_q),
                    baseline_gap=self.baseline_gap,
                    ticks_since_swap=tick - self.served_at)
                dsp.add(triggered=bool(stats.triggered))
            if stats.triggered:
                self._start_resolve(cold=False)   # warm: from the served Q
                self._advance_resolve()

        # 3) queries against whatever is served right now
        with jl.span("query_drain", "serving", tick=tick) as qsp:
            rng = np.random.default_rng(cfg.seed * 31 + 17 + tick)
            for j in range(cfg.queries_per_tick):
                req_id = tick * cfg.queries_per_tick + j
                self.queries.submit(req_id, rng.standard_normal(cfg.d))
            answered = len(self.queries.process(self.served_q))
            expired = self.queries.drain_expired()
            qsp.add(answered=answered, drain_expired=expired)

        # 4) staleness: served-from freeze step vs ingested step — a
        #    surfaced metric, never a stall
        staleness = (self.ingestor.step - self.served_stream_step
                     if self.swaps else 0)
        self.max_staleness = max(self.max_staleness, staleness)
        self.registry.gauge("serving_staleness_ticks").set(staleness)
        self.history.append({
            "tick": tick, "staleness": staleness, "swaps": self.swaps,
            "resolve_active": self.resolve_active,
            "resolve_done": self.resolve_done if self.resolve_active else 0})

        # 5) commit the tick (blocking: pins must follow a published step);
        #    a kill at this boundary re-executes the whole tick, which is a
        #    pure function of the previous snapshot
        self.tick = tick
        self.state_mgr.save(tick, self._tree(), blocking=True)
        if self.served_at == tick:
            # pin the snapshot holding the just-swapped subspace; retire
            # older pins so exactly the last-good generation survives GC
            self.state_mgr.pin(tick)
            for s in self.state_mgr.pinned_steps():
                if s != tick:
                    self.state_mgr.unpin(s)
        tick_sp.end(staleness=staleness, swaps=self.swaps)

    def run(self, until: Optional[int] = None) -> "PSAService":
        stop = self.cfg.total_ticks if until is None else until
        while self.tick + 1 < stop:
            self._run_tick()
        return self

    # -- reporting ----------------------------------------------------------
    def summary(self) -> dict:
        served = np.asarray(self.served_q, np.float32)
        return {
            "tick": self.tick,
            "swaps": self.swaps,
            "gate_rejects": self.gate_rejects,
            "cold_resolves": self.cold_resolves,
            "served_at": self.served_at,
            "served_stream_step": self.served_stream_step,
            "max_staleness": self.max_staleness,
            "served_sha256": hashlib.sha256(served.tobytes()).hexdigest(),
            "queries": self.queries.summary(),
        }

    def finalize(self) -> dict:
        """Publish the completion marker the supervisor looks for."""
        doc = self.summary()
        with open(os.path.join(self.workdir, _FINAL), "w") as f:
            json.dump(doc, f, indent=2)
        obs_dir = obs_dir_for(self.workdir)
        if obs_dir is not None:
            # the aggregate twin of the journal: the obs CLI merges this
            # dump (query latency histogram, swap/reject counters) into its
            # exposition alongside journal-derived span durations
            self.registry.dump(os.path.join(obs_dir, "metrics.service.json"))
        return doc


# ---------------------------------------------------------------------------
# event-log digest (trajectory comparison across runs)
# ---------------------------------------------------------------------------
def service_summary(workdir: str) -> dict:
    """final.json + the deduplicated event trajectory.

    Events are append-only across restarts, so a re-executed tick appends
    byte-identical duplicates; dedup keeps the FIRST occurrence per
    (type, tick, resolve_id) key. The swap/reject tick lists are the
    served-subspace trajectory two runs are compared on."""
    with open(os.path.join(workdir, _FINAL)) as f:
        doc = json.load(f)
    events, seen = [], set()
    path = os.path.join(workdir, _EVENTS)
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                ev = json.loads(line)
                key = (ev["type"], ev["tick"], ev.get("resolve_id"))
                if key in seen:
                    continue
                seen.add(key)
                events.append(ev)
    doc["swap_ticks"] = [e["tick"] for e in events if e["type"] == "swap"]
    doc["reject_ticks"] = [e["tick"] for e in events if e["type"] == "reject"]
    doc["restores"] = [e for e in events if e["type"] == "restore"]
    return doc


# ---------------------------------------------------------------------------
# supervision: subprocess + heartbeat watchdog + relaunch with backoff
# ---------------------------------------------------------------------------
def run_supervised(cfg: ServiceConfig, workdir: str, *,
                   stall_timeout: float = 8.0, startup_timeout: float = 240.0,
                   poll: float = 0.3, max_relaunches: int = 6,
                   backoff: float = 0.25, env: Optional[dict] = None,
                   verbose: bool = False) -> dict:
    """Run the service to completion in a supervised subprocess.

    The child heartbeats at every service-tick and re-solve-chunk save; the
    supervisor kills it when the beat goes stale (a wedged process — e.g. a
    chaos ``hang`` — stops beating but never exits) and relaunches with
    linear backoff. Beats are PROGRESS beats: a beat older than this
    attempt's spawn counts as "not yet started", judged against the more
    generous ``startup_timeout`` (first tick pays jax import + compile)."""
    os.makedirs(workdir, exist_ok=True)
    spec = os.path.join(workdir, "service.json")
    cfg.to_json(spec)
    beat_path = os.path.join(workdir, _HEARTBEAT)
    final_path = os.path.join(workdir, _FINAL)
    attempts, relaunches = 0, 0
    while True:
        attempts += 1
        spawn_t = time.time()
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.serving.service", "--run", spec,
             "--workdir", workdir],
            env=dict(env) if env is not None else os.environ.copy())
        while True:
            rc = proc.poll()
            if rc is not None:
                break
            now = time.time()
            beat = os.path.getmtime(beat_path) \
                if os.path.exists(beat_path) else 0.0
            if beat > spawn_t:
                stale = now - beat > stall_timeout
            else:
                stale = now - spawn_t > startup_timeout
            if stale:
                proc.kill()
                proc.wait()
                rc = "stalled"
                break
            time.sleep(poll)
        if verbose:
            print(f"[supervisor] attempt {attempts}: rc={rc}")
        if rc == 0 and os.path.exists(final_path):
            break
        if rc == EXIT_NO_DEVICE:
            raise RuntimeError("service child found no TPU on a host that "
                               "has one; another process holds the chip")
        if relaunches >= max_relaunches:
            raise RuntimeError(
                f"service did not complete within {max_relaunches} "
                f"relaunches (last rc={rc})")
        relaunches += 1
        time.sleep(backoff * relaunches)
    doc = service_summary(workdir)
    doc["attempts"] = attempts
    doc["relaunches"] = relaunches
    return doc


# ---------------------------------------------------------------------------
# seeded serving-chaos smoke scenario (CI entry point)
# ---------------------------------------------------------------------------
def run_smoke(workdir: str, *, verbose: bool = True) -> dict:
    """The CI serving-chaos scenario (see module docstring).

    (b) runs first: until its supervised child is done, this process opens
    no JAX backend, so on a one-chip host the child gets the chip.

    (a) fault-free in-process reference run;
    (b) the same config supervised under kill-mid-service /
        kill-mid-re-solve / hang faults — the served trajectory (swap
        ticks AND served bits) must be identical to (a), every restore
        must match the pinned last-good snapshot, and the three faults
        must cost exactly three relaunches;
    (c) a corrupt-candidate + delay-query plan in-process — the gate must
        reject the mangled candidate (never serving it), recover through a
        cold re-solve to a subspace close to the post-shift truth, and
        delayed queries must expire against their deadline instead of
        blocking the loop.
    """
    cfg = ServiceConfig()
    os.makedirs(workdir, exist_ok=True)

    # (b) kill/kill/hang under supervision: trajectory must be identical
    chaos_dir = os.path.join(workdir, "chaos")
    os.makedirs(chaos_dir, exist_ok=True)
    plan = FaultPlan(seed=0, faults=[
        # tick-7 save killed: the tick (ingest + resolve increment) is
        # lost and re-executed after relaunch
        {"kind": "kill", "worker": "service", "boundary": 7},
        # re-solve chunk-boundary save at absolute step 6 killed: the
        # re-solve resumes bit-identically from its RunState checkpoint
        {"kind": "kill", "worker": "resolve", "boundary": 6},
        # wedge at tick 12 without exiting: the heartbeat goes stale and
        # the supervisor's watchdog kills + relaunches
        {"kind": "hang", "worker": "service", "boundary": 12, "sleep": 60},
    ])
    plan_path = plan.dump(os.path.join(chaos_dir, "plan.json"))
    env = os.environ.copy()
    env[ENV_PLAN] = plan_path
    t0 = time.perf_counter()
    chaos = run_supervised(cfg, chaos_dir, env=env, verbose=verbose)
    chaos_s = time.perf_counter() - t0

    # (a) fault-free reference
    ref_dir = os.path.join(workdir, "ref")
    PSAService(cfg, ref_dir).run().finalize()
    ref = service_summary(ref_dir)
    assert ref["swaps"] >= 2, ref          # initial solve + >=1 drift swap
    assert ref["gate_rejects"] == 0, ref
    assert ref["max_staleness"] <= cfg.staleness_bound, ref
    assert ref["queries"]["answered"] > 0, ref

    assert chaos["relaunches"] == 3, chaos
    # the served-subspace trajectory is BIT-identical to the reference
    assert chaos["served_sha256"] == ref["served_sha256"], (chaos, ref)
    assert chaos["swap_ticks"] == ref["swap_ticks"], (chaos, ref)
    assert chaos["swaps"] == ref["swaps"], (chaos, ref)
    assert chaos["gate_rejects"] == 0, chaos
    assert chaos["max_staleness"] <= cfg.staleness_bound, chaos
    # every restore that had a pin matched it bitwise; at least one did
    matches = [e["pinned_match"] for e in chaos["restores"]]
    assert all(m is not False for m in matches), chaos["restores"]
    assert any(m is True for m in matches), chaos["restores"]

    # (c) corrupt-candidate + delayed queries, in-process
    gate_dir = os.path.join(workdir, "gate")
    gate_plan = FaultPlan(seed=0, faults=[
        # mangle the FIRST drift-triggered warm re-solve's candidate
        {"kind": "corrupt_candidate", "mode": "nan", "resolve": 1},
        # and delay ~40% of queries past their deadline
        {"kind": "delay_query", "p": 0.4, "delay": 0.5},
    ])
    svc = PSAService(cfg, gate_dir, plan=gate_plan).run()
    gate = svc.finalize()
    assert gate["gate_rejects"] == 1, gate       # the mangled candidate
    assert gate["cold_resolves"] == 1, gate      # ... fell back cold
    assert gate["swaps"] >= 2, gate              # ... and recovered
    assert np.all(np.isfinite(svc.served_q))     # NaN never served
    from ..core.metrics import subspace_error
    post_err = float(subspace_error(svc.q_post,
                                    jnp.asarray(svc.served_q)))
    assert post_err < 0.2, post_err              # recovered to the truth
    assert gate["queries"]["expired"] > 0, gate  # delays expired, not slept
    assert gate["max_staleness"] <= cfg.staleness_bound, gate

    summary = {
        "ref": {k: ref[k] for k in ("swaps", "swap_ticks", "served_sha256",
                                    "max_staleness")},
        "chaos": {"relaunches": chaos["relaunches"],
                  "restores": len(chaos["restores"]),
                  "trajectory_bitwise_equal": True,
                  "wall_s": round(chaos_s, 2)},
        "gate": {"gate_rejects": gate["gate_rejects"],
                 "cold_resolves": gate["cold_resolves"],
                 "post_shift_subspace_err": round(post_err, 4),
                 "queries": gate["queries"]},
    }
    if verbose:
        print(json.dumps(summary, indent=2))
    return summary


def main(argv=None) -> int:
    import argparse
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--run", metavar="SPEC",
                    help="run a service to total_ticks from a JSON config")
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="run the seeded serving-chaos CI scenario")
    args = ap.parse_args(argv)
    if args.smoke:
        workdir = args.workdir or tempfile.mkdtemp(prefix="serving_smoke_")
        run_smoke(workdir)
        return 0
    if not args.run:
        ap.error("nothing to do (pass --run SPEC or --smoke)")
    init_compile_cache()
    try:
        require_expected_platform()
    except RuntimeError as e:
        print(e, file=sys.stderr)
        return EXIT_NO_DEVICE
    cfg = ServiceConfig.from_json(args.run)
    workdir = args.workdir or os.path.dirname(os.path.abspath(args.run))
    plan_path = os.environ.get(ENV_PLAN)
    plan = FaultPlan.load(plan_path) if plan_path else None
    svc = PSAService(cfg, workdir, plan=plan).run()
    svc.finalize()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
