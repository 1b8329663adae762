"""End-to-end federated trainer: server loop + FedHC resource simulation.

Each global round is an explicit phased state machine
(:class:`RoundPhase`):

  ``SAMPLE``    sample participants (with optional over-selection), obtain
                each one's *framework-provided* runtime (measured wall
                clock of its real jitted workload, or the analytical
                compiled-cost backend), draw failure times and the
                deadline;
  ``SIMULATE``  drive the FedHC campaign engine (scheduler + process
                manager + sharing under one continuous clock, with every
                SPAWN/COMPLETE/FAIL mirrored through the FLServer control
                plane) to get the round's simulated timeline;
  ``DISPATCH``  pick the round's finishers and, when a control-plane
                dispatcher is injected, broadcast params to the remote
                workers;
  ``COLLECT``   run the *actual* local training — one finisher per step,
                so a fabric can interleave this wall-clock work with other
                tenants' phases;
  ``AGGREGATE`` sync weighted FedAvg, or FedBuff-style async ordered by
                simulated completion times, with optional uplink
                compression;
  ``REPORT``    evaluate, record history, checkpoint (atomic, keep-k,
                resumable).

``run_round()`` simply loops :meth:`FederatedTrainer.step_round` until the
round is ``DONE`` — the legacy Python-synchronous behaviour, bit-identical
to the pre-state-machine trainer.  A ``repro.core.fabric.PoolFabric`` can
instead drive the phases itself (``PoolFabric.run_trainers``): the trainer
enqueues its round spec (:meth:`submit_round`), subscribes to the engine's
round-boundary callbacks, and the fabric's merged event loop invokes the
wall-clock phase steps between simulated events so N trainer tenants
genuinely interleave.  The phase table (which phases burn wall clock vs
simulated clock) is documented in docs/architecture.md § 4.1.

The simulated clock is the x-axis of the convergence figures (Fig 8/9d);
failure injection + deadline + over-selection exercise the fault-tolerance
path (clients that die are simply absent from aggregation).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt.checkpoint import CheckpointManager
from repro.core.aggregation import AsyncAggregator, apply_deltas
from repro.core.budget import ClientBudget, WorkloadSpec
from repro.core.campaign import CampaignEngine, RoundResult, RoundSpec
from repro.core.runtime import MeasuredRuntime
from repro.core.scheduler import SCHEDULERS
from repro.core.simulator import SimClient
from repro.data.pipeline import ClientDataset
from repro.fed.client import FLClient, make_small_step
from repro.fed.compression import (
    compress_tree, decompress_tree, is_compressed_tree, tree_wire_bytes,
)
from repro.models.small import SmallModelConfig, init_small, small_loss
from repro.obs.metrics import Counter
from repro.obs.trace import span
from repro.optim.optimizers import make_optimizer

PyTree = Any


@dataclass
class FedConfig:
    rounds: int = 20
    participants_per_round: int = 10
    local_steps: int = 10
    scheduler: str = "fedhc"            # fedhc | greedy
    theta: float = 100.0                # >100 enables soft-margin sharing
    manager_mode: str = "dynamic"       # dynamic | fixed
    max_parallel: int = 32
    aggregation: str = "fedavg"         # fedavg | async
    async_buffer: int = 4
    server_lr: float = 1.0
    prox_mu: float = 0.0
    optimizer: str = "sgd"
    learning_rate: float = 0.05
    compression: str = "none"           # none | int8 | topk
    client_batching: str = "off"        # off | wave (batched COLLECT)
    over_select_frac: float = 0.0       # fault tolerance: sample extra clients
    deadline_frac: Optional[float] = None  # deadline = frac × slowest expected
    failure_rate: float = 0.0           # P(client dies mid-round)
    seed: int = 0
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 5


class RoundPhase(Enum):
    """States of the per-round trainer state machine.  Transitions are
    strictly forward (SAMPLE → … → DONE); every phase step is resumable,
    so an external driver (the fabric) can interleave steps of N trainers.
    """

    SAMPLE = "sample"          # wall clock: runtime probes, RNG draws
    SIMULATE = "simulate"      # fabric clock: the engine's event loop
    DISPATCH = "dispatch"      # wall clock: finisher pick / remote broadcast
    COLLECT = "collect"        # wall clock: one real local training per step
    AGGREGATE = "aggregate"    # wall clock: FedAvg / async apply
    REPORT = "report"          # wall clock: eval, history, checkpoint
    DONE = "done"


#: each phase step's span: ``fedhc.phase.<phase>`` in the profiler trace
_PHASE_SPANS = {ph: f"phase.{ph.value}" for ph in RoundPhase}


@dataclass
class RoundState:
    """Mutable per-round state threaded through the phase steps.  One
    round in flight per trainer; ``run_round`` owns it on the legacy path,
    the fabric's trainer driver owns it when the fabric owns the clock."""

    phase: RoundPhase = RoundPhase.SAMPLE
    participants: List[FLClient] = field(default_factory=list)
    by_id: Dict[int, FLClient] = field(default_factory=dict)
    works: Dict[int, float] = field(default_factory=dict)
    failure_times: Dict[int, float] = field(default_factory=dict)
    deadline: Optional[float] = None
    result: Optional[RoundResult] = None
    engine_round_idx: Optional[int] = None   # set by submit_round (fabric)
    finishers: List[Tuple[int, Any]] = field(default_factory=list)
    remote: Optional[list] = None            # dispatcher round results
    trainable: List[int] = field(default_factory=list)  # eager-collect queue
    mode: str = "FULL"                       # FULL | DEGRADED (quorum close)
    deltas: List[Tuple[PyTree, float]] = field(default_factory=list)
    train_metrics: Dict[str, float] = field(default_factory=dict)
    collect_idx: int = 0                     # finishers collected so far
    rec: Optional[dict] = None               # the round's history record


class FederatedTrainer:
    def __init__(
        self,
        mcfg: SmallModelConfig,
        clients: Sequence[FLClient],
        fed: FedConfig,
        test_batch: Optional[Dict[str, np.ndarray]] = None,
        engine: Optional[CampaignEngine] = None,
        runtime=None,
        dispatcher=None,
        obs=None,
    ):
        """``runtime`` (optional) overrides the framework-provided runtime
        backend (default: wall-clock ``MeasuredRuntime``; inject a
        deterministic one to make the simulated timeline reproducible
        across hosts).  ``dispatcher`` (optional) makes local training
        *remote*: instead of calling ``client.train_local`` in-process, the
        round's finishers are trained by worker processes driven over the
        control plane — see ``repro.launch.multihost.ControlPlaneDispatcher``.
        """
        self.mcfg = mcfg
        self.clients = list(clients)
        self.fed = fed
        self.test_batch = test_batch
        self.rng = np.random.default_rng(fed.seed)
        self.runtime = runtime if runtime is not None else MeasuredRuntime()
        self.dispatcher = dispatcher
        self.opt = make_optimizer(fed.optimizer, fed.learning_rate)
        self.step_fn = make_small_step(mcfg, self.opt, fed.prox_mu)
        self.params = init_small(jax.random.PRNGKey(fed.seed), mcfg)
        self.sim_clock = 0.0
        self.round = 0
        self.obs = obs
        self._subscribed = False         # engine round-boundary callbacks
        self._active_st: Optional["RoundState"] = None  # submitted round
        # identity on the shared obs plane: spans land on a per-tenant
        # track and metrics in a per-tenant scope.  An injected fabric
        # engine names the tenant; the engine default ("campaign") and the
        # no-engine case keep the legacy "trainer" identity.
        tenant = getattr(engine, "tenant", None) if engine is not None else None
        self.tenant = "trainer" if tenant in (None, "campaign") else tenant
        self._trace = (obs.tracer if obs is not None and obs.tracer.enabled
                       else None)
        # aggregation-payload bytes (post-compression deltas); distinct from
        # the mirror's control-plane bytes and the transport's framed bytes
        self._comm = (obs.registry.counter("fed.comm_bytes", self.tenant)
                      if obs is not None else Counter())
        self._h_train = (obs.registry.histogram("client.train_seconds",
                                                self.tenant)
                         if obs is not None else None)
        # host-resident deltas the fold moved to the device
        self._fold_h2d = (obs.registry.counter("fold.h2d_bytes", self.tenant)
                          if obs is not None else Counter())
        self._m_degraded = (obs.registry.counter("round.degraded",
                                                 self.tenant)
                            if obs is not None else Counter())
        self.history: List[dict] = []
        self.async_agg = AsyncAggregator(
            buffer_size=fed.async_buffer, server_lr=fed.server_lr
        )
        # one campaign engine for the whole run: continuous simulated clock
        # across rounds, executor pool persists, and every simulated
        # SPAWN/COMPLETE/FAIL is mirrored through the FLServer control plane.
        # An injected engine is a *tenant handle*: a fabric tenant
        # (PoolFabric.add_tenant) shares its slot pool with other jobs —
        # this trainer then draws executors through the arbiter's lease,
        # and fed.scheduler/theta/manager_mode/max_parallel are the
        # injected engine's, not this config's.
        self.engine = engine if engine is not None else CampaignEngine(
            SCHEDULERS[fed.scheduler],
            theta=fed.theta,
            manager_mode=fed.manager_mode,
            max_parallel=fed.max_parallel,
            mirror=True,
            obs=obs,
            # lifelong engine: per-round timelines feed the history records,
            # but the campaign-global timeline and executor event history
            # would grow without bound over a long training run
            record_campaign_timeline=False,
            record_events=False,
        )
        # batched COLLECT: one compiled program per wave of finishers
        # (opt-in — the sequential path stays the bit-identity reference)
        self.batch_exec = None
        if fed.client_batching == "wave":
            from repro.fed.batch_exec import BatchedExecutor

            self.batch_exec = BatchedExecutor(
                mcfg, self.opt, fed.prox_mu, obs=obs, tenant=self.tenant
            )
        # eval function built ONCE: a fresh `jax.jit(lambda ...)` per round
        # is a new callable identity, so it recompiled every round
        self._eval_fn = (
            jax.jit(lambda p, b: small_loss(p, self.mcfg, b))
            if test_batch is not None else None
        )
        self.ckpt = (
            CheckpointManager(fed.ckpt_dir, keep=3) if fed.ckpt_dir else None
        )

    @property
    def comm_bytes(self) -> int:
        return int(self._comm.value)

    @comm_bytes.setter
    def comm_bytes(self, v: int) -> None:
        self._comm.reset(int(v))

    # ------------------------------------------------------------------
    def _client_work_seconds(self, client: FLClient, opt_state) -> float:
        """Framework-provided runtime: wall-clock one real jitted step, scale
        by the client's data volume (steps).  ``opt_state`` is the round's
        shared probe state — params shape is invariant across participants,
        so one ``opt.init`` per round serves every timing probe."""
        wl = client.workload
        batch = client.data.next_batch()
        key = (self.mcfg.kind, wl.n_layers, wl.seq_len, wl.batch_size,
               self.mcfg.extra_local_model, batch["x"].shape)
        sec = self.runtime.seconds_at_full(
            key,
            lambda p, o, b: self.step_fn(p, o, b, p)[0],
            (self.params, opt_state, batch),
            n_steps=wl.n_batches,
        )
        return sec

    def _sample(self) -> List[FLClient]:
        n = self.fed.participants_per_round
        n_sel = min(len(self.clients), int(np.ceil(n * (1 + self.fed.over_select_frac))))
        idx = self.rng.choice(len(self.clients), size=n_sel, replace=False)
        return [self.clients[i] for i in idx]

    # ------------------------------------------------------------------
    # The phased round state machine.  Each _step_* method performs one
    # resumable unit of work and advances st.phase; run_round() loops them
    # synchronously, PoolFabric.run_trainers interleaves them across
    # tenants at the merged clock's event boundaries.
    # ------------------------------------------------------------------

    def begin_round(self) -> RoundState:
        return RoundState()

    def step_round(self, st: RoundState) -> RoundPhase:
        """Execute the next phase step of the round; returns the phase the
        round is in afterwards.  COLLECT consumes one step per finisher, so
        a driver calling ``step_round`` repeatedly makes incremental
        wall-clock progress it can interleave with other work."""
        if st.phase is not RoundPhase.DONE:
            with span(_PHASE_SPANS[st.phase], self._trace, self.tenant, "rounds",
                      round=self.round):
                self._PHASE_STEPS[st.phase](self, st)
        return st.phase

    def _step_sample(self, st: RoundState) -> None:
        fed = self.fed
        st.participants = self._sample()
        # one probe opt-state for the whole round: params shape is
        # invariant across participants, so per-client re-init was waste
        probe_opt_state = self.opt.init(self.params)
        st.works = {c.client_id: self._client_work_seconds(c, probe_opt_state)
                    for c in st.participants}
        st.by_id = {c.client_id: c for c in st.participants}

        # failure injection: each selected client may die partway through
        st.failure_times = {}
        for c in st.participants:
            if self.rng.random() < fed.failure_rate:
                frac = self.rng.uniform(0.1, 0.9)
                st.failure_times[c.client_id] = (
                    frac * st.works[c.client_id] / (c.budget / 100.0)
                )

        st.deadline = None
        if fed.deadline_frac is not None:
            worst = max(w / (c.budget / 100.0) for c, w in
                        [(c, st.works[c.client_id]) for c in st.participants])
            st.deadline = fed.deadline_frac * worst
        st.phase = RoundPhase.SIMULATE

    def _sim_clients(self, st: RoundState) -> List[SimClient]:
        return [SimClient(c.client_id, c.budget, st.works[c.client_id])
                for c in st.participants]

    def _step_simulate(self, st: RoundState) -> None:
        """Legacy synchronous path: drive our own engine to round close.
        A fabric-driven trainer never enters here — ``submit_round``
        enqueues the spec and the fabric steps the engine instead."""
        st.result = self.engine.run_round(
            self._sim_clients(st), deadline=st.deadline,
            failure_times=st.failure_times,
        )
        st.phase = RoundPhase.DISPATCH

    def submit_round(self, st: RoundState) -> int:
        """Fabric path for SIMULATE: queue the round's spec into the engine
        WITHOUT driving the clock (the fabric owns the merged event loop).
        Subscribes (once) to the engine's round-boundary callbacks: each
        simulated COMPLETE feeds the eager-collection queue, and round
        close delivers the result (``complete_simulate``) — the phase
        stays SIMULATE until then."""
        assert st.phase is RoundPhase.SIMULATE and st.engine_round_idx is None
        if not self._subscribed:
            self.engine.on_client_done(self._engine_client_done)
            self.engine.on_round_complete(self._engine_round_complete)
            self._subscribed = True
        self._active_st = st
        spec = RoundSpec(
            clients=tuple(self._sim_clients(st)),
            deadline=st.deadline,
            failure_times=dict(st.failure_times),
        )
        st.engine_round_idx = self.engine.enqueue_rounds([spec])[0].idx
        return st.engine_round_idx

    def _engine_client_done(self, cid: int, round_idx: int) -> None:
        st = self._active_st
        if st is not None and st.engine_round_idx == round_idx:
            st.trainable.append(cid)

    def _engine_round_complete(self, round_idx: int, result) -> None:
        st = self._active_st
        if st is not None and st.engine_round_idx == round_idx:
            self._active_st = None
            self.complete_simulate(st, result)

    def complete_simulate(self, st: RoundState, result: RoundResult) -> None:
        """Deliver the simulated round result (from the engine's
        ``on_round_complete`` callback); unblocks the wall-clock phases."""
        st.result = result
        st.phase = RoundPhase.DISPATCH

    def collect_eager(self, st: RoundState) -> bool:
        """Train one client whose *simulated* completion already fired
        (``on_client_done``) while the round is still SIMULATE — the wall
        work no longer waits for the round's straggler tail.  Completions
        arrive in span-end order, exactly the finisher order DISPATCH
        would pick, so eager collection is bit-identical to collecting
        after the fact.  Returns True if a client was trained."""
        if st.phase is not RoundPhase.SIMULATE or self.dispatcher is not None:
            return False
        # over-selection: only the first participants_per_round completions
        # become finishers — never train past that cap
        cap = min(len(st.trainable), self.fed.participants_per_round)
        if st.collect_idx >= cap:
            return False
        self._collect_client(st, st.trainable[st.collect_idx])
        return True

    def collect_wave_eager(self, st: RoundState) -> int:
        """Batched variant of :meth:`collect_eager`: drain *all* clients
        whose simulated COMPLETE has fired (up to the finisher cap) in one
        compiled wave.  Falls back to the per-client eager step when
        batching is off.  Returns the number of clients trained."""
        if self.batch_exec is None:
            return int(self.collect_eager(st))
        if st.phase is not RoundPhase.SIMULATE or self.dispatcher is not None:
            return 0
        cap = min(len(st.trainable), self.fed.participants_per_round)
        if st.collect_idx >= cap:
            return 0
        cids = st.trainable[st.collect_idx:cap]
        self._collect_wave(st, cids)
        return len(cids)

    def _step_dispatch(self, st: RoundState) -> None:
        fed = self.fed
        n_target = fed.participants_per_round
        st.finishers = sorted(
            st.result.spans.items(), key=lambda kv: kv[1].end
        )[:n_target]
        if self.dispatcher is not None:
            with span("round.broadcast", self._trace, self.tenant, "rounds",
                      round=self.round, clients=len(st.finishers)):
                st.remote = self.dispatcher.train_round(
                    [cid for cid, _ in st.finishers], self.params,
                    fed.local_steps, self.round, compression=fed.compression,
                )
            report = getattr(self.dispatcher, "last_round_report", None)
            if report is not None and report.get("mode") == "DEGRADED":
                # quorum close: the dispatcher returned results for the
                # reported subset only — drop the stragglers' finisher
                # slots so COLLECT/AGGREGATE see matching lists and the
                # FedAvg weight sum renormalizes over the survivors
                # (identical math to the simulator's straggler drop)
                reported = set(report.get("reported", ()))
                st.finishers = [f for f in st.finishers if f[0] in reported]
                st.mode = "DEGRADED"
                if st.result is not None:
                    st.result.mode = "DEGRADED"
                self._m_degraded.inc()
                if self._trace is not None:
                    self._trace.wall_instant(
                        "round.degraded", self.tenant, "rounds",
                        args={"round": self.round,
                              "reported": len(st.finishers),
                              "stragglers": len(report.get("stragglers", ()))})
        st.phase = RoundPhase.COLLECT

    def _collect_client(self, st: RoundState, cid: int) -> None:
        """Train/ingest ONE finisher (st.collect_idx'th): the real local
        training in-process, or the matching remote result; compression and
        comm accounting ride along.  Shared by the COLLECT phase step and
        the eager path."""
        fed = self.fed
        if st.remote is not None:
            delta, n_seen, m = st.remote[st.collect_idx]
        else:
            with span("client.train", self._trace, self.tenant, "train",
                      cid=cid, round=self.round) as sp:
                delta, n_seen, m = st.by_id[cid].train_local(
                    self.params, self.step_fn, self.opt, n_steps=fed.local_steps
                )
            if self._h_train is not None:
                self._h_train.observe(sp.seconds)
        self._ingest_delta(st, cid, delta, n_seen, m)

    def _ingest_delta(self, st: RoundState, cid: int, delta, n_seen, m) -> None:
        """Compression + comm accounting + delta bookkeeping for one
        collected client — shared by the per-client and batched-wave
        paths, with identical per-client compression seeds."""
        fed = self.fed
        if fed.compression != "none":
            # workers compress at the source (the delta travels the
            # wire compressed — wire codec v2 transmits it natively);
            # the in-process path quantizes here with the same seed, so
            # both paths dequantize to identical bits
            if st.remote is None or not is_compressed_tree(delta):
                delta = compress_tree(
                    delta, fed.compression, seed=self.round * 1000 + cid
                )
            self._comm.inc(tree_wire_bytes(delta))
            delta = decompress_tree(delta)
        else:
            # leaf nbytes need no pull: a device delta stays on the device
            self._comm.inc(sum(l.nbytes for l in jax.tree.leaves(delta)))
        st.deltas.append((delta, float(n_seen)))
        st.train_metrics = m
        st.collect_idx += 1

    def _collect_wave(self, st: RoundState, cids: List[int]) -> None:
        """Train a whole wave of finishers as ONE compiled program
        (``BatchedExecutor.run_wave``), then ingest the per-client results
        in the same order — aggregation order and compression seeds are
        identical to collecting the clients one at a time.  The deltas
        arrive on the device, as the sequential path's do."""
        with span("client.batch_wave", self._trace, self.tenant, "train",
                  round=self.round, clients=len(cids)) as sp:
            results = self.batch_exec.run_wave(
                self.params, [st.by_id[c] for c in cids],
                self.fed.local_steps, self.round,
            )
            lw = self.batch_exec.last_wave
            sp.set(mode=lw.get("mode"), cache_hit=lw.get("cache_hit"))
        for cid, (delta, n_seen, m) in zip(cids, results):
            self._ingest_delta(st, cid, delta, n_seen, m)

    def _step_collect(self, st: RoundState) -> None:
        if st.collect_idx < len(st.finishers):
            if self.batch_exec is not None and st.remote is None:
                # batched fast path: drain every remaining finisher in one
                # compiled wave (remote dispatch keeps the per-client loop)
                self._collect_wave(
                    st, [cid for cid, _ in st.finishers[st.collect_idx:]])
            else:
                self._collect_client(st, st.finishers[st.collect_idx][0])
        if st.collect_idx >= len(st.finishers):
            st.phase = RoundPhase.AGGREGATE

    def _step_aggregate(self, st: RoundState) -> None:
        fed = self.fed
        if st.deltas:
            with span("round.aggregate", self._trace, self.tenant, "rounds",
                      round=self.round, deltas=len(st.deltas)):
                if fed.aggregation == "async":
                    for (delta, w), _ in zip(st.deltas, st.finishers):
                        if self.async_agg.add(delta, w, self.round):
                            self.params = self.async_agg.flush(self.params,
                                                               h2d=self._fold_h2d)
                else:
                    self.params = apply_deltas(self.params, st.deltas, fed.server_lr,
                                               tracer=self._trace, pid=self.tenant,
                                               h2d=self._fold_h2d)
        st.phase = RoundPhase.REPORT

    def _step_report(self, st: RoundState) -> None:
        result = st.result
        self.sim_clock = self.engine.now
        self.round += 1

        rec = {
            "round": self.round,
            "duration": result.duration,
            "sim_clock": self.sim_clock,
            "completed": len(st.deltas),
            "mode": st.mode,
            "failed": len(result.failed),
            "avg_parallelism": result.avg_parallelism(),
            "utilization": result.utilization(),
            "comm_bytes": self.comm_bytes,
            **{f"train_{k}": v for k, v in st.train_metrics.items()},
        }
        if self.dispatcher is not None:
            # bytes actually framed onto the wire (both directions), from
            # the dispatcher's transport counters — split into the tensor
            # payload share vs framing/header overhead
            rec.update(self.dispatcher.wire_stats())
        if self.test_batch is not None:
            loss, m = self._eval_fn(self.params, self.test_batch)
            rec["test_loss"] = float(loss)
            rec["test_acc"] = float(m["acc"])
        self.history.append(rec)

        if self.ckpt and self.round % self.fed.ckpt_every == 0:
            meta = {
                "sim_clock": self.sim_clock,
                "comm_bytes": self.comm_bytes,
                # snapshot: the async-write worker must not see rounds
                # appended after this save
                "history": list(self.history),
            }
            if self.obs is not None:
                # counter continuity across resume: the registry's counter
                # values ride the checkpoint meta so a restored campaign's
                # comm/wire counters (and obs.report()) continue instead of
                # restarting at zero
                meta["counters"] = self.obs.registry.counters_snapshot()
            self.ckpt.save(self.round, self.params, meta)
        st.rec = rec
        st.phase = RoundPhase.DONE

    _PHASE_STEPS: Dict[RoundPhase, Callable] = {
        RoundPhase.SAMPLE: _step_sample,
        RoundPhase.SIMULATE: _step_simulate,
        RoundPhase.DISPATCH: _step_dispatch,
        RoundPhase.COLLECT: _step_collect,
        RoundPhase.AGGREGATE: _step_aggregate,
        RoundPhase.REPORT: _step_report,
    }

    # ------------------------------------------------------------------
    def run_round(self) -> dict:
        """The legacy synchronous round: loop the state machine to DONE on
        this thread (the trainer owns the clock)."""
        st = self.begin_round()
        while st.phase is not RoundPhase.DONE:
            self.step_round(st)
        return st.rec

    def maybe_restore(self) -> bool:
        """Resume from the latest checkpoint if one exists — params AND the
        simulated clock/history/comm counters, so the convergence x-axis
        (Fig 8/9d) continues instead of restarting at t=0.  Returns True
        when a checkpoint was restored."""
        if not self.ckpt:
            return False
        step, params, meta = self.ckpt.restore_latest_with_meta(self.params)
        if step is None:
            return False
        self.params = params
        self.round = step
        self.sim_clock = float(meta.get("sim_clock", 0.0))
        self.comm_bytes = int(meta.get("comm_bytes", 0))
        self.history = list(meta.get("history", []))
        # continue the campaign clock (never rewind a shared fabric clock)
        self.engine.now = max(self.engine.now, self.sim_clock)
        if self.obs is not None and meta.get("counters"):
            # re-seed every checkpointed counter (engine + trainer scopes)
            # so campaign/wire accounting stays monotone across the resume
            self.obs.registry.restore_counters(meta["counters"])
        return True

    def run(self, rounds: Optional[int] = None) -> List[dict]:
        self.maybe_restore()
        n = self.fed.rounds if rounds is None else rounds
        for _ in range(n):
            self.run_round()
        return self.history


# --------------------------------------------------------------------------
# Convenience builder for the paper-style experiments
# --------------------------------------------------------------------------


def build_fl_clients(
    mcfg: SmallModelConfig,
    budgets: Sequence[ClientBudget],
    dataset: str = "femnist",
    n_samples: int = 4000,
    alpha: float = 0.5,
    batch_size: int = 32,
    n_batches: int = 10,
    seed: int = 0,
) -> Tuple[List[FLClient], Dict[str, np.ndarray]]:
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import make_dataset

    n_test = 512
    x_all, y_all = make_dataset(dataset, n_samples + n_test, seed=seed)
    x, y = x_all[:n_samples], y_all[:n_samples]
    xt, yt = x_all[n_samples:], y_all[n_samples:]
    parts = dirichlet_partition(y, len(budgets), alpha=alpha, seed=seed)
    clients = []
    for cb, part in zip(budgets, parts):
        if len(part) < 2:
            part = np.arange(2)
        ds = ClientDataset(x[part], y[part], batch_size, seed=seed + cb.client_id)
        clients.append(
            FLClient(
                cb.client_id,
                cb.budget,
                ds,
                WorkloadSpec(
                    model=mcfg.kind,
                    n_layers=mcfg.n_layers,
                    batch_size=batch_size,
                    n_batches=n_batches,
                    extra_local_model=mcfg.extra_local_model,
                ),
            )
        )
    return clients, {"x": xt, "y": yt}
