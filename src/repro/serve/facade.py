"""``repro.serve.LLM`` — the single serving front door (ISSUE 5).

The two serving engines grew divergent constructor kwarg piles
(``DecodeEngine(slots=…, cache_len=…)`` vs
``ContinuousBatchingScheduler(rows=…, page_size=…, num_pages=…,
attn_path=…, kv_quant=…)``). The facade replaces both entry points with one
object resolved around a :class:`repro.core.plan.ServePlan`:

    plan = core.plan.plan_serve(cfg, hbm_budget_bytes=…, expected_batch=…,
                                expected_len_dist={"mean": …, "max": …})
    llm = repro.serve.LLM(cfg, params, plan)
    done = llm.generate([(prompt, max_new), ...])          # drain semantics
    done = llm.stream(requests, on_token=callback)         # continuous batch

* :meth:`generate` drains a fixed request list to completion on the dense
  slot engine (``serve.engine.DecodeEngine``) — the batch-throughput path.
* :meth:`stream` serves arriving requests with continuous batching over the
  plan's paged (or contiguous) KV layout
  (``serve.scheduler.ContinuousBatchingScheduler``) and per-token callbacks
  — the latency/goodput path.

Both wrapped engines read every dispatch decision from the same plan, so
switching between the two entry points can never flip a kernel route
mid-deployment. ``plan=None`` resolves a conservative default plan (half
the per-chip HBM, modest batch) — explicit plans are the production path.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Union

from repro.core import eyexam, plan as plan_lib
from repro.serve import shard as shard_lib
from repro.serve.engine import DecodeEngine, Request
from repro.serve.guard import GuardConfig
from repro.serve.replica import ReplicaSet
from repro.serve.scheduler import ContinuousBatchingScheduler, StreamRequest
from repro.serve.telemetry import Telemetry

DEFAULT_LEN_DIST = {"mean": 256, "max": 512}
DEFAULT_BATCH = 8


RequestLike = Union[Request, StreamRequest, Dict, tuple]


class LLM:
    """One model + one resolved ServePlan, served two ways.

    ``eos_id``/``temperature`` are request-stream sampling semantics (not
    dispatch decisions), so they stay constructor kwargs; everything that
    picks a kernel path, a memory layout, or a capacity lives in ``plan``.
    Engines are built lazily and reused across calls (their jitted programs
    and donated cache buffers are warm after the first call).
    """

    def __init__(self, cfg, params, plan: Optional[plan_lib.ServePlan] = None,
                 *, eos_id: int = 1, temperature: float = 0.0,
                 guard: Union[GuardConfig, None, bool] = None,
                 replicas: int = 1,
                 on_token: Optional[Callable] = None,
                 on_outcome: Optional[Callable] = None,
                 trace: Union[bool, Telemetry] = True):
        if replicas < 1:
            raise ValueError(
                f"replicas must be >= 1, got {replicas}: serving always "
                "goes through at least one scheduler replica (replicas=1 "
                "is the single-scheduler fast path, replicas>=2 the "
                "fault-tolerant control plane)")
        if plan is None:
            plan = plan_lib.plan_serve(
                cfg,
                hbm_budget_bytes=int(eyexam.device_peaks().hbm_bytes // 2),
                expected_batch=DEFAULT_BATCH,
                expected_len_dist=dict(DEFAULT_LEN_DIST))
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.eos_id = eos_id
        self.temperature = temperature
        # robustness guard (ISSUE 6): on by default behind the facade — every
        # streamed request ends in a structured RequestOutcome, overload is
        # shed/degraded along the plan's ladder instead of raising. Pass
        # ``guard=False`` for the raw legacy engine behavior, or a tuned
        # GuardConfig for production deadlines/budgets.
        if guard is None:
            guard = GuardConfig()
        elif guard is False:
            guard = None
        self.guard: Optional[GuardConfig] = guard
        # multi-replica control plane (ISSUE 7): replicas >= 2 serves
        # stream() through a ReplicaSet — router placement, heartbeats,
        # deterministic failover — on the same plan and guard
        self.replicas = replicas
        # constructor-level streaming defaults: a deployment that always
        # wants the same callbacks sets them once here; per-call arguments
        # (and per-request callbacks) still override
        self.on_token = on_token
        self.on_outcome = on_outcome
        # observability (serve.telemetry, ISSUE 8): one Telemetry bundle
        # shared by whichever engine serves, reset at each call. trace=True
        # records spans on the virtual step clock (deterministic; wall time
        # as annotations); trace=False keeps the metrics registry but drops
        # span recording; passing a Telemetry shares an external bundle.
        if isinstance(trace, Telemetry):
            self._telemetry = trace
        else:
            self._telemetry = Telemetry(enabled=bool(trace))
        self._engine: Optional[DecodeEngine] = None
        self._scheduler: Optional[ContinuousBatchingScheduler] = None
        self._replicaset: Optional[ReplicaSet] = None
        self._last_run = None                # engine behind the last call

    # ------------------------------------------------------------- helpers
    def explain(self) -> str:
        """The plan's per-decision Eyexam rationale."""
        return self.plan.explain()

    @property
    def mesh(self) -> shard_lib.ServeMesh:
        """The plan's resolved serving mesh (ISSUE 10) — ``tp=1 ep=1`` for
        unsharded plans. Sharded plans serve through the same two entry
        points: the models read ``tp``/``ep`` off the active plan, so both
        ``generate`` and ``stream`` execute the shard-explicit program."""
        return shard_lib.ServeMesh.from_plan(self.plan)

    def sharding_report(self) -> Dict:
        """Mesh + per-device pool stats for the most recent call: resolved
        tp/ep, the devices that hold the weights and the KV cache, single-
        vs per-device KV pool bytes, and (after a sharded paged ``stream``)
        live per-shard occupancy and the lockstep-divergence count."""
        sched = self._scheduler
        return shard_lib.sharding_stats(
            self.cfg, self.plan, pool=getattr(sched, "pager", None),
            params=self.params,
            pool_devices=getattr(sched, "pool_devices", None))

    def _normalize(self, requests: Sequence[RequestLike], cls,
                   on_token: Optional[Callable] = None) -> List:
        """Accept engine Request/StreamRequest objects, dicts, or
        (prompt, max_new) tuples; auto-assign rids by input position."""
        out = []
        for i, r in enumerate(requests):
            if isinstance(r, cls):
                pass
            elif isinstance(r, (Request, StreamRequest)):
                r = cls(rid=r.rid, prompt=list(r.prompt), max_new=r.max_new)
            elif isinstance(r, dict):
                r = cls(**{"rid": i, **r})
            else:
                prompt, max_new = r
                r = cls(rid=i, prompt=list(prompt), max_new=int(max_new))
            if cls is StreamRequest and on_token is not None \
                    and r.on_token is None:
                r.on_token = on_token
            out.append(r)
        if len({r.rid for r in out}) != len(out):
            raise ValueError("request rids must be unique")
        return out

    def _validate(self, requests: Sequence) -> None:
        """Caller-bug checks at the front door (ISSUE 6 satellite): empty
        batches and infeasible requests raise a clear ValueError naming the
        violated limit, before any engine is built or any work is traced —
        runtime faults, by contrast, become RequestOutcomes, never raises."""
        if not requests:
            raise ValueError(
                "empty request list — nothing to serve (did request "
                "construction upstream filter everything out?)")
        patches = self.cfg.num_patches if self.cfg.frontend == "vision" else 0
        cache_len = self.plan.cache_len
        for r in requests:
            if not r.prompt and not patches:
                raise ValueError(
                    f"request {r.rid}: empty prompt — decode needs at least "
                    "one conditioning token")
            plen = len(r.prompt) + patches
            if plen + max(r.max_new, 0) > cache_len:
                raise ValueError(
                    f"request {r.rid}: prompt ({plen} tokens"
                    f"{' incl. vision patches' if patches else ''}) + "
                    f"max_new ({r.max_new}) = {plen + max(r.max_new, 0)} "
                    f"exceeds the plan's cache_len ({cache_len}); shorten "
                    "the request or re-plan with a larger "
                    "expected_len_dist['max']")

    # ------------------------------------------------------------- serving
    def generate(self, requests: Sequence[RequestLike], rng=None
                 ) -> List[Request]:
        """Drain ``requests`` to completion (batch-throughput semantics).

        Wraps the dense-slot ``DecodeEngine``; returns the finished request
        objects in input order (``r.out`` holds the generated tokens).
        """
        if self._engine is None:
            self._engine = DecodeEngine(
                self.cfg, self.params, self.plan, eos_id=self.eos_id,
                temperature=self.temperature, telemetry=self._telemetry)
        self._last_run = self._engine
        reqs = self._normalize(requests, Request)
        self._validate(reqs)
        self._telemetry.reset()            # one trace per call
        done = self._engine.run(reqs, rng=rng)
        return sorted(done, key=lambda r: r.rid)

    def stream(self, requests: Sequence[RequestLike],
               on_token: Optional[Callable] = None, rng=None,
               on_outcome: Optional[Callable] = None, chaos=None
               ) -> List[StreamRequest]:
        """Serve ``requests`` with continuous batching + streaming.

        Wraps the paged ``ContinuousBatchingScheduler`` (requests may carry
        ``arrival`` stamps and per-request ``on_token`` callbacks; a
        call-level ``on_token(request, token)`` applies to any request
        without its own, falling back to the constructor-level default, as
        does ``on_outcome(request, outcome)``). With the default guard every
        returned request carries a terminal ``r.outcome``
        (ok/shed/expired/preempted_out/failed). With ``replicas >= 2`` the
        call serves through the multi-replica control plane
        (``serve.replica.ReplicaSet``): router placement, heartbeat
        supervision, deterministic failover. ``chaos`` takes a
        ``serve.chaos.ChaosConfig`` (or, multi-replica, a
        ``ReplicaChaosConfig``) for deterministic fault injection (tests/CI
        only). Returns finished requests in input order.
        """
        on_token = on_token if on_token is not None else self.on_token
        on_outcome = on_outcome if on_outcome is not None \
            else self.on_outcome
        reqs = self._normalize(requests, StreamRequest, on_token=on_token)
        self._validate(reqs)
        if on_outcome is not None:
            for r in reqs:
                if r.on_outcome is None:
                    r.on_outcome = on_outcome
        if self.replicas > 1:
            if self._replicaset is None:
                self._replicaset = ReplicaSet(
                    self.cfg, self.params, self.plan,
                    replicas=self.replicas, eos_id=self.eos_id,
                    temperature=self.temperature, guard=self.guard,
                    telemetry=self._telemetry)
            self._last_run = self._replicaset
            # ReplicaSet.run resets the shared bundle itself
            return self._replicaset.run(reqs, rng=rng, chaos=chaos)
        if self._scheduler is None:
            self._scheduler = ContinuousBatchingScheduler(
                self.cfg, self.params, self.plan, eos_id=self.eos_id,
                temperature=self.temperature, guard=self.guard,
                telemetry=self._telemetry)
        self._last_run = self._scheduler
        self._telemetry.reset()            # one trace per call
        done = self._scheduler.run(reqs, rng=rng, chaos=chaos)
        return sorted(done, key=lambda r: r.rid)

    # ------------------------------------------------------------- reports
    @property
    def phase_stats(self) -> Dict:
        """Phase stats of the most recently run entry point (prefill/decode
        split, paging/sharing counters)."""
        return self._last_run.phase_stats if self._last_run is not None \
            else {}

    def telemetry(self) -> Telemetry:
        """The Telemetry bundle of the most recent call: ``.tracer`` (spans
        on the virtual step clock; ``to_chrome_trace()`` for Perfetto),
        ``.metrics`` (frozen-key registry; ``snapshot()``), and
        ``.last_drift`` (Eyexam-at-runtime DriftReport vs the plan)."""
        return self._telemetry
