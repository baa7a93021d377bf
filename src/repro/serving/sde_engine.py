"""Batched Monte-Carlo sampling engine: a façade over scheduler + executor.

The SDE analogue of the LM :class:`~repro.serving.engine.Engine`: requests
(solver name, horizon, number of paths) join a FIFO queue; the engine serves
them in *fixed-size* ticks of ``slots`` trajectories, filling each tick with
paths from as many compatible queued requests as fit (continuous batching).
A request larger than ``slots`` is served across several ticks.

Since PR 5 the engine is a thin façade over two layers (see
``docs/serving.md``):

* :class:`repro.serving.scheduler.Scheduler` — host-side: FIFO queue,
  signature grouping, slot-plan construction, result scatter/retirement,
  cancellation, ``pending()`` introspection.  Pure Python, unit-testable
  without a device.
* :class:`repro.serving.executor.TickExecutor` — device-side: runs a
  same-signature *stack* of tick key-buffers through one jit'd
  on-device multi-tick loop (:func:`repro.core.sdeint_ticks`), so
  ``ticks_per_dispatch`` ticks cost ONE host round trip instead of one
  each; with ``mesh_axis`` set, each tick's slot axis additionally shards
  over a device mesh (``slots = devices x per_device_slots``).

Three properties make the slicing and the dispatch grouping safe:

* path ``i`` of request ``r`` always uses ``fold_in(base_key_r, i)``, so the
  sample a request receives is independent of slot assignment, tick
  boundaries, dispatch depth, and device placement;
* ``sdeint``'s batch is bitwise equal to single-trajectory solves, and
  ``sdeint_ticks``'s on-device tick loop is bitwise equal to per-tick
  dispatch — so multi-tick, single-tick, and mesh-sharded serving all
  return identical bits (regression-tested);
* compiled executables are cached per request *signature* (solver spec,
  horizon, step count, save cadence, adaptive tolerances / output grid) and
  stack depth — steady-state serving never recompiles.

Since PR 6 the engine **double-buffers** by default: jax dispatch is
asynchronous, so right after a stack is handed to the device the engine
plans and key-packs the *next* stack (``Scheduler.plan(reserve=True)``)
while the device is still integrating — reservations keep the cursor
arithmetic identical to plan-after-deliver, so the plan sequence and all
samples are bitwise-unchanged (``double_buffer=False`` restores the strict
sequential loop).  ``submit`` takes a ``priority`` class and is bounded by
``max_queue_requests`` / ``max_queue_paths`` admission control
(:class:`QueueFull`); :class:`repro.serving.AsyncSDESampleEngine` builds the
fully asynchronous, cross-signature-interleaving serving plane on the same
two layers (see ``docs/serving.md``).

Adaptive requests (an ``"ees25:adaptive"``-style spec) run the single
forward-only controller pass (``bounded=False`` — sampling needs no second
sweep; bitwise-identical to realize-then-solve) on a Virtual Brownian Tree —
paths in one batch each walk their own accept/reject step sequence under
vmap — and remain reproducible offline from the seed: the result surfaces
each path's realized-grid stats (``n_accepted`` / ``n_rejected`` /
``t_final``), and a client can realize the identical grid offline with
:func:`repro.core.adaptive.realize_grid` + ``solve`` under any adjoint,
including the O(1)-memory reversible one, for gradient work on served
samples.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import parse_solver_spec, select_solver
from .bucketing import BucketKey, BucketingConfig, bucket_key, group_key
from .executor import TickExecutor
from .scheduler import (
    STAT_FIELDS,
    QueueFull,
    RetryPolicy,
    SampleRequest,
    SampleResult,
    Scheduler,
    SlotPlan,
    make_request,
)

__all__ = ["SDESampleConfig", "SampleRequest", "SampleResult",
           "SDESampleEngine", "QueueFull", "RetryPolicy"]


@dataclasses.dataclass(frozen=True)
class SDESampleConfig:
    slots: int = 64            # trajectories integrated per tick
    dtype: Any = jnp.float32
    ticks_per_dispatch: int = 1  # ticks per host round trip (on-device loop)
    mesh: Any = None             # device mesh to shard the slot axis over
    mesh_axis: Optional[str] = None  # mesh axis name (slots % axis size == 0)
    # Host-side double buffering: build + key-pack slot plan N+1 while the
    # device still runs stack N (jax dispatch is asynchronous, so the host
    # work overlaps device compute).  Plan sequence and samples are
    # bitwise-unchanged; False restores strict plan-after-deliver.
    double_buffer: bool = True
    # Admission control: bound the live queue (requests / owed paths); a
    # submit over either limit raises QueueFull instead of growing the
    # queue without bound.  None = unbounded (the PR-5 behaviour).
    max_queue_requests: Optional[int] = None
    max_queue_paths: Optional[int] = None
    # Signature coalescing (PR 8): pad eligible fixed-grid requests up a
    # powers-of-two step ladder so signatures that differ only in horizon
    # length share one executable and stack into the same dispatch —
    # bitwise-identical to exact dispatch (see repro.serving.bucketing).
    # False is the exact opt-out: one executable per signature.
    bucketing: bool = True
    bucket_min_steps: int = 8
    # Directory for jax's persistent compilation cache: compiled serving
    # executables are written to disk and reloaded by later processes, so a
    # restarted engine warm-starts instead of re-paying XLA compilation.
    # Routed through repro.compile_cache.enable_compile_cache, so a set
    # JAX_COMPILATION_CACHE_DIR wins over this path.
    compile_cache_dir: Optional[str] = None
    # Divergence guard (PR 9): every solve carries the in-loop blow-up check
    # (non-finite state, or |y| > guard_threshold) and delivers a per-path
    # ``diverged`` flag — a pure observer, so guarded samples are
    # bitwise-identical to unguarded ones.  None disables the guard (and
    # with it retry-on-divergence).  float('inf') checks non-finiteness only.
    guard_threshold: Optional[float] = 1e6
    # Degradation ladder for requests whose delivered paths diverged: halve
    # the step, then fall back to the wide-stability ees27 scheme, at most
    # max_retries resubmits per request (seeded — retries are reproducible).
    # None turns retries off (diverged results retire flagged, unretried).
    retry_policy: Optional[RetryPolicy] = RetryPolicy()
    # Supervised async serve loop: how many times an injected/transient
    # executor crash may restart the loop before it fails the engine.
    max_restarts: int = 2


class SDESampleEngine:
    """Serve Monte-Carlo sampling requests against one SDE term.

    ``term``/``y0``/``args`` define the process; each request picks a solver
    from the registry by name and a horizon.  Results come back as stacked
    numpy arrays per request id (like ``Engine.done``).  The engine itself
    only wires the host-side :class:`~repro.serving.scheduler.Scheduler` to
    the device-side :class:`~repro.serving.executor.TickExecutor` and turns
    slot plans into key buffers.
    """

    def __init__(self, term, y0, cfg: SDESampleConfig = SDESampleConfig(),
                 args: Any = None, noise_shape=None, clock=None):
        if cfg.ticks_per_dispatch < 1:
            raise ValueError(
                f"ticks_per_dispatch must be >= 1, got {cfg.ticks_per_dispatch}"
            )
        if (cfg.mesh is None) != (cfg.mesh_axis is None):
            # A long-lived engine must not depend on whatever mesh context
            # happens to be ambient at dispatch time — and slots/axis
            # divisibility has to be checkable here, not at the queue head.
            raise ValueError(
                "sharded serving needs mesh and mesh_axis together; pass "
                "both in SDESampleConfig (e.g. make_sample_mesh() + 'mc')"
            )
        if cfg.mesh is not None:
            axis = cfg.mesh.shape[cfg.mesh_axis]
            if cfg.slots % axis != 0:
                raise ValueError(
                    f"slots={cfg.slots} must be a multiple of mesh axis "
                    f"{cfg.mesh_axis!r} (size {axis}) to shard the slot axis"
                )
        self.term = term
        self.y0 = y0
        self.cfg = cfg
        self.args = args
        self.noise_shape = noise_shape
        if cfg.compile_cache_dir is not None:
            enable_compile_cache(cfg.compile_cache_dir)
        self._bucket_cfg = BucketingConfig(enabled=cfg.bucketing,
                                           min_steps=cfg.bucket_min_steps)
        self.scheduler = Scheduler(
            max_requests=cfg.max_queue_requests,
            max_paths=cfg.max_queue_paths,
            group_key=lambda sig: group_key(sig, self._bucket_cfg),
            clock=clock,
        )
        self.executor = TickExecutor(
            term, y0, args=args, noise_shape=noise_shape, dtype=cfg.dtype,
            mesh=cfg.mesh, mesh_axis=cfg.mesh_axis,
            guard=cfg.guard_threshold,
        )
        self._key_cache: Dict[int, np.ndarray] = {}
        self._pad_key = np.asarray(jax.random.PRNGKey(0))
        # Double buffering: the (reserved plan, packed key stack) staged
        # while the device ran the previous dispatch.
        self._staged: Optional[Tuple[SlotPlan, jax.Array]] = None
        # Robustness bookkeeping (PR 9).  Retry children run under NEGATIVE
        # internal ids (never colliding with user ids, never shifting the
        # default-seed id counter) and keep the ROOT request's seed, so a
        # retried sample is exactly what submitting the degraded spec
        # directly would produce.  Counters are cumulative over the engine's
        # lifetime — see pending(detail=True) / AsyncSDESampleEngine.drain.
        self._retry_ids = itertools.count(1)
        self._retry_parent: Dict[int, int] = {}   # child rid -> root rid
        self._retry_attempt: Dict[int, int] = {}  # root rid -> retries spent
        self._req_by_id: Dict[int, SampleRequest] = {}
        self._deadline: Dict[int, float] = {}     # root rid -> absolute s
        self.counters: Dict[str, int] = {
            "retries": 0, "timeouts": 0, "diverged_requests": 0,
            "diverged_paths": 0, "restarts": 0,
        }

    # The queue, result store, and compiled-executable cache live on the two
    # layers; these views keep the engine's original surface (and tests).
    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def done(self) -> Dict[int, SampleResult]:
        return self.scheduler.done

    @property
    def _compiled(self):
        return self.executor._compiled

    def submit(self, solver: str, *, t1: float, n_steps: int, n_paths: int,
               t0: float = 0.0, save_every: Optional[int] = None,
               seed: Optional[int] = None, rtol: Optional[float] = None,
               atol: Optional[float] = None, save_at=None,
               priority: int = 0,
               deadline_ms: Optional[float] = None) -> int:
        """Queue a sampling request; returns its request id.

        Parameters
        ----------
        solver:
            Registry spec string — ``"ees25"``, ``"mcf-rk4:lam=0.99"``,
            ``"ees25:adaptive"``, ...  An ``adaptive`` flag switches the
            request to tolerance-driven stepping on a Virtual Brownian Tree;
            ``n_steps`` then bounds trial steps instead of fixing a grid.
            ``"auto"`` (or ``"auto:stiffness=<lam>"``) defers the choice to
            :func:`repro.core.registry.select_solver`, fed with the engine
            term's declared noise mode and the request's step size — the
            resolved spec is what gets compiled and cached, so two requests
            that auto-select the same solver share an executable.
        t0, t1:
            Integration window (``t1 > t0``).
        n_steps:
            Grid size (fixed) or trial-step budget (adaptive).
        n_paths:
            Trajectories to sample; large requests are served across ticks.
        save_every:
            Fixed grid only: save the state every that many steps (must
            divide ``n_steps``); results gain a ``(n_paths, n_saves, ...)``
            ``ys``.
        seed:
            Base seed; path ``i`` uses ``fold_in(PRNGKey(seed), i)``, so
            results are reproducible offline regardless of batching.
            Defaults to the request id.
        rtol, atol:
            Adaptive only: controller tolerances (defaults 1e-4 / 1e-6).
        save_at:
            Adaptive only: sequence of output times in ``[t0, t1]`` — dense
            output interpolated between accepted steps.
        priority:
            Service class (default 0): higher priorities are planned sooner;
            equal priorities keep strict FIFO.  Priority reorders *when* a
            request is served, never its samples (pure function of
            ``(seed, path)``).
        deadline_ms:
            Wall-clock budget in milliseconds.  A request not fully
            delivered when it expires retires into ``done`` with
            ``timed_out=True`` and no arrays (the async engine instead wakes
            the waiter with ``TimeoutError``).  The sync engine checks
            deadlines once per dispatch cycle, so expiry resolution is one
            dispatch.  Retries inherit the remaining budget.

        Raises
        ------
        ValueError / KeyError on any malformed option — always here at
        submit time, never inside jit at the queue head.
        :class:`~repro.serving.scheduler.QueueFull` when admission control
        (``max_queue_requests`` / ``max_queue_paths``) rejects the request.

        Example
        -------
        >>> rid = eng.submit("ees25:adaptive", t1=2.0, n_steps=256,
        ...                  n_paths=1000, rtol=1e-3, save_at=[0.5, 1.0, 2.0])
        >>> eng.run()[rid].ys.shape
        (1000, 3, ...)
        """
        if isinstance(solver, str):
            name, auto_kw = parse_solver_spec(solver)
            if name == "auto":
                unknown = set(auto_kw) - {"stiffness", "noise"}
                if unknown:
                    raise ValueError(
                        f"unknown option {sorted(unknown)[0]!r} for solver "
                        "'auto'; valid keys: noise, stiffness"
                    )
                auto_kw.setdefault(
                    "noise", getattr(self.term, "noise", "diagonal"))
                solver = select_solver(
                    dt=(t1 - t0) / max(int(n_steps), 1), **auto_kw)
        term_kind = ("manifold" if hasattr(self.term, "algebra_increment")
                     else "euclidean")
        # Validate against the *peeked* id: a rejected submit must not burn
        # an id (default seeds equal the request id, so a burned id would
        # shift every later request's samples).
        req = make_request(
            self.scheduler.next_request_id, solver, term_kind=term_kind,
            t0=t0, t1=t1, n_steps=n_steps, n_paths=n_paths,
            save_every=save_every, seed=seed, rtol=rtol, atol=atol,
            save_at=save_at, priority=priority, deadline_ms=deadline_ms,
        )
        rid = self.scheduler.enqueue(req)
        self._req_by_id[rid] = req
        if deadline_ms is not None:
            self._deadline[rid] = self.scheduler.clock() + deadline_ms / 1e3
        return rid

    def pending(self, detail: bool = False) -> Dict[int, Any]:
        """Paths still owed per queued request id — poll this between ticks
        (cancelled requests drop out; completed ones move to ``done``).

        ``detail=True`` returns per-request dicts instead of bare counts:
        ``remaining`` plus the coalescing introspection — ``bucket`` (the
        :class:`~repro.serving.bucketing.BucketKey` the request was planned
        into, None before planning or for exact dispatch),
        ``n_padded_steps`` (masked padding steps per path),
        ``n_padded_paths`` (dead slots delivered alongside it so far),
        ``n_diverged`` (delivered paths the blow-up guard flagged) and
        ``deadline_remaining_s``.  The detail dict additionally carries one
        non-request entry, ``"counters"``: the engine-lifetime robustness
        counters (``retries`` / ``timeouts`` / ``diverged_requests`` /
        ``diverged_paths`` / ``restarts``)."""
        out = self.scheduler.pending(detail=detail)
        if detail:
            out["counters"] = dict(self.counters)
        return out

    def warmup(self, signatures) -> int:
        """Ahead-of-time compile the executables a list of requests needs.

        ``signatures`` is a list of submit-style dicts — ``{"solver": ...,
        "t1": ..., "n_steps": ...}`` plus any of ``t0`` / ``save_every`` /
        ``rtol`` / ``atol`` / ``save_at`` — describing expected traffic
        (``n_paths`` / ``seed`` / ``priority`` are ignored: executables
        depend only on the signature).  Each is resolved to its bucket (or
        exact signature) and AOT-compiled at the configured ``slots`` for
        both dispatch depths the engine uses (``ticks_per_dispatch`` and the
        single-tick tail).  With ``compile_cache_dir`` set this also
        populates the on-disk cache, so later processes warm-start.  Returns
        the number of executables actually compiled by this call (already
        cached entries — in memory or on disk — are cheap no-ops and do not
        count)."""
        fresh = 0
        for spec in signatures:
            spec = dict(spec)
            for drop in ("n_paths", "seed", "priority"):
                spec.pop(drop, None)
            solver = spec.pop("solver")
            term_kind = ("manifold" if hasattr(self.term, "algebra_increment")
                         else "euclidean")
            req = make_request(0, solver, term_kind=term_kind,
                               n_paths=1, seed=0, **spec)
            key = bucket_key(req.signature, self._bucket_cfg)
            if key is None:
                key = req.signature
            for depth in {1, self.cfg.ticks_per_dispatch}:
                fresh += self.executor.warmup(key, depth, self.cfg.slots)
        return fresh

    def cancel(self, request_id: int) -> bool:
        """Cancel a queued request (partial results discarded).  True if this
        call cancelled it; False if already cancelled or already completed;
        ``KeyError`` on unknown ids.  A request mid-retry is cancellable by
        its ROOT id — the queued degraded child (internal negative id) is
        what actually gets cancelled."""
        target = request_id
        if (request_id in self._retry_attempt
                and request_id not in self.scheduler.done):
            for child, root in self._retry_parent.items():
                if root == request_id:
                    target = child
                    break
        cancelled = self.scheduler.cancel(target)
        if cancelled:
            self._key_cache.pop(target, None)
            self._req_by_id.pop(target, None)
            self._deadline.pop(request_id, None)
            self._retry_attempt.pop(request_id, None)
            if target != request_id:
                self._retry_parent.pop(target, None)
                # The root id is what clients hold — record its cancellation
                # so re-cancels return False and async result() raises
                # CancelledError instead of KeyError.
                self.scheduler._cancelled_ids.add(request_id)
        return cancelled

    # -- robustness internals (PR 9) ----------------------------------------

    def _expire(self) -> list:
        """Retire queued requests whose deadline passed; book the timeouts.

        A timed-out retry child resolves to its ROOT id — the child never
        surfaces (its negative id is internal), the root lands in ``done``
        with ``timed_out=True``.  Returns the expired ROOT ids (what the
        async plane wakes waiters on)."""
        roots = []
        for rid in self.scheduler.expire_deadlines():
            self.counters["timeouts"] += 1
            self._key_cache.pop(rid, None)
            self._req_by_id.pop(rid, None)
            root = self._retry_parent.pop(rid, rid)
            attempt = self._retry_attempt.pop(root, 0)
            self._deadline.pop(root, None)
            res = self.scheduler.done.pop(rid)
            self.scheduler.done[root] = dataclasses.replace(
                res, retries=attempt)
            roots.append(root)
        return roots

    def _make_retry(self, root: int, req: SampleRequest,
                    attempt: int) -> Optional[int]:
        """Enqueue the degraded resubmit of ``req`` (retry ``attempt``);
        None when no retry is possible (deadline spent, or the degraded spec
        does not validate — e.g. a manifold term with a euclidean fallback)."""
        policy = self.cfg.retry_policy
        deadline_ms = None
        dl = self._deadline.get(root)
        if dl is not None:
            remaining = dl - self.scheduler.clock()
            if remaining <= 0:
                return None
            deadline_ms = remaining * 1e3
        overrides = policy.degrade(req, attempt)
        n_steps = overrides.get("n_steps", req.n_steps)
        save_every = req.save_every
        if save_every is not None and n_steps != req.n_steps:
            # Halved h doubles the grid; scale the cadence so the retried
            # result saves the same times (and the same number of frames).
            save_every = save_every * (n_steps // req.n_steps)
        term_kind = ("manifold" if hasattr(self.term, "algebra_increment")
                     else "euclidean")
        child_id = -next(self._retry_ids)
        try:
            child = make_request(
                child_id, overrides.get("solver", req.solver),
                term_kind=term_kind, t0=req.t0, t1=req.t1, n_steps=n_steps,
                n_paths=req.n_paths, save_every=save_every, seed=req.seed,
                rtol=req.rtol, atol=req.atol, save_at=req.save_at,
                priority=req.priority, deadline_ms=deadline_ms)
        except ValueError:
            return None
        # force: a retry replaces capacity an earlier admit already granted;
        # refusing it would strand the request (and any async waiter).
        self.scheduler.enqueue(child, force=True)
        self._req_by_id[child_id] = child
        self._retry_parent[child_id] = root
        self._retry_attempt[root] = attempt + 1
        self.counters["retries"] += 1
        return child_id

    def _finalize_retired(self, rid: int) -> Optional[int]:
        """Post-retirement hook: book divergence, retry or surface.

        Called with an id just retired into ``done``.  Returns the ROOT id
        now terminally complete (results of retry children move under their
        root), or None when the request went back on the queue as a
        degraded retry.  Forces a host read of the per-path ``diverged``
        flags — one tiny bool array per retired request, NOT per tick."""
        res = self.scheduler.done[rid]
        root = self._retry_parent.get(rid, rid)
        attempt = self._retry_attempt.get(root, 0)
        n_div = 0
        if res.diverged is not None:
            n_div = int(np.asarray(jax.device_get(res.diverged)).sum())
        if n_div:
            self.counters["diverged_requests"] += 1
            self.counters["diverged_paths"] += n_div
        req = self._req_by_id.get(rid)
        if (n_div and self.cfg.retry_policy is not None and req is not None
                and attempt < self.cfg.retry_policy.max_retries
                and self._make_retry(root, req, attempt) is not None):
            del self.scheduler.done[rid]
            self._req_by_id.pop(rid, None)
            if rid != root:
                self._retry_parent.pop(rid, None)
            return None
        self._req_by_id.pop(rid, None)
        self._retry_attempt.pop(root, None)
        self._deadline.pop(root, None)
        if rid != root:
            self._retry_parent.pop(rid, None)
            res = self.scheduler.done.pop(rid)
            self.scheduler.done[root] = res
        if attempt:
            self.scheduler.done[root] = dataclasses.replace(
                self.scheduler.done[root], retries=attempt)
        return root

    # -- internals -----------------------------------------------------------

    def _request_keys(self, req: SampleRequest) -> np.ndarray:
        """All of a request's path keys, built once: one vmapped
        ``fold_in(PRNGKey(seed), i)`` over the path indices (integer ops —
        bitwise-identical to per-path host calls)."""
        keys = self._key_cache.get(req.request_id)
        if keys is None:
            from repro.core.sdeint import path_keys

            keys = np.asarray(
                path_keys(jax.random.PRNGKey(req.seed), req.n_paths))
            self._key_cache[req.request_id] = keys
        return keys

    def _plan_keys(self, plan: SlotPlan) -> jax.Array:
        """Assemble the (n_ticks, slots, ...) key stack for one dispatch;
        unassigned slots get a dummy key (their outputs are never read), so
        every dispatch of a (signature, depth) pair reuses one executable."""
        buf = np.empty((plan.n_ticks, plan.slots) + self._pad_key.shape,
                       self._pad_key.dtype)
        buf[:] = self._pad_key
        for t, tick in enumerate(plan.ticks):
            s = 0
            while s < len(tick):  # contiguous (pending, path) runs -> slices
                p, i0 = tick[s]
                e = s + 1
                while e < len(tick) and tick[e][0] is p:
                    e += 1
                buf[t, s:e] = self._request_keys(p.request)[i0:i0 + (e - s)]
                s = e
        return jnp.asarray(buf)

    def _split_subplans(self, plan: SlotPlan) -> list:
        """Split a plan into dispatch units that only ever touch the full
        ``ticks_per_dispatch`` stack executable or the single-tick one.

        A plan shallower than the configured depth (the queue tail, or a
        ``max_ticks``-capped budget) is served tick-by-tick through the
        single-tick executable rather than as a fresh variable-depth stack —
        otherwise every distinct tail depth would trigger a full XLA
        recompile of the solve, and a drain would touch up to
        ``ticks_per_dispatch`` executables per signature instead of two."""
        if plan.n_ticks in (1, self.cfg.ticks_per_dispatch):
            return [plan]
        return [SlotPlan(plan.tick_sigs[t] if plan.tick_sigs else
                         plan.signature, plan.slots, [tick],
                         reserved=plan.reserved, group=plan.group,
                         tick_sigs=(plan.tick_sigs[t],)
                         if plan.tick_sigs else None)
                for t, tick in enumerate(plan.ticks)]

    def _exec_key(self, plan: SlotPlan):
        """What the executor caches/dispatches on for this plan: its bucket
        when the scheduler grouped it into one, else its exact signature."""
        if isinstance(plan.group, BucketKey):
            return plan.group
        return plan.signature

    def _active_steps(self, plan: SlotPlan):
        """The bucket executable's per-tick true-step-count operand (None for
        exact dispatch).  Each tick is signature-homogeneous by planner
        contract, so its entry is that tick's signature's ``n_steps``."""
        if not isinstance(plan.group, BucketKey):
            return None
        return jnp.asarray([sig[3] for sig in plan.tick_sigs], jnp.int32)

    def _dispatch(self, plan: SlotPlan, keys):
        """Route one subplan to the executor — bucketed or exact."""
        return self.executor.dispatch(self._exec_key(plan), keys,
                                      self._active_steps(plan))

    def _take_plan(self, depth: int):
        """The next (plan, key stack) to dispatch: the staged pair when it is
        still live and fits the tick budget, else a fresh reserved plan.

        A staged stack whose every request was cancelled since staging is
        *released*, never dispatched — a fully-cancelled stack must not burn
        a no-op device dispatch (regression-tested: ``n_dispatches`` stays
        flat when a cancel empties the queue mid-run)."""
        while self._staged is not None:
            plan, keys = self._staged
            self._staged = None
            if not plan.live:
                self.scheduler.release(plan)   # skip, don't dispatch no-ops
                continue
            if plan.n_ticks > depth:
                # The budget shrank since staging (run(max_ticks=...) tail):
                # unwind the reservation — staged is always the newest plan,
                # so LIFO release is safe — and replan at the allowed depth.
                self.scheduler.release(plan)
                continue
            return plan, keys
        plan = self.scheduler.plan(self.cfg.slots, depth, reserve=True)
        if plan is None:
            return None, None
        return plan, self._plan_keys(plan)

    def _stage_next(self) -> None:
        """Plan and key-pack the next dispatch while the device is still
        running the current one (host-side double buffering): reservations
        make the cursor arithmetic identical to planning after delivery, so
        the plan sequence — and therefore every sample — is unchanged."""
        if self._staged is None:
            plan = self.scheduler.plan(self.cfg.slots,
                                       self.cfg.ticks_per_dispatch,
                                       reserve=True)
            if plan is not None:
                self._staged = (plan, self._plan_keys(plan))

    def _dispatch_next(self, tick_limit: int) -> int:
        """Plan (or unstage), dispatch, and deliver one tick stack; returns
        the number of ticks served (0 when idle — nothing live queued).

        Crash safety: if a dispatch raises (an injected executor fault, an
        XLA error), the reservations of every not-yet-delivered tick are
        released before the exception propagates — the queue keeps owning
        exactly the undelivered work, so a caller that catches the error and
        calls ``run()`` again serves every path exactly once (no loss, no
        duplication; samples are key-determined, so the rerun is bitwise
        what an uninterrupted run would have delivered)."""
        self._expire()
        depth = min(tick_limit, self.cfg.ticks_per_dispatch)
        plan, keys = self._take_plan(depth)
        if plan is None:
            return 0
        subplans = self._split_subplans(plan)
        offset = 0
        delivered = 0
        try:
            for i, sp in enumerate(subplans):
                sp_keys = keys if len(subplans) == 1 else \
                    keys[offset:offset + sp.n_ticks]
                offset += sp.n_ticks
                result = self._dispatch(sp, sp_keys)
                if i == len(subplans) - 1 and self.cfg.double_buffer:
                    # Device is (asynchronously) chewing on the stack we just
                    # dispatched; overlap the next plan's host work with it.
                    self._stage_next()
                outputs = {"y_final": np.asarray(result.y_final),
                           "ys": (None if result.ys is None
                                  else np.asarray(result.ys))}
                # Adaptive results carry where each path actually stopped
                # plus its realized-grid stats; the guard adds the per-path
                # diverged flag — surface them all so truncated paths are
                # detectable, step counts observable, and blow-ups
                # retryable.
                for name in STAT_FIELDS:
                    val = getattr(result, name, None)
                    outputs[name] = None if val is None else np.asarray(val)
                for rid in self.scheduler.deliver(sp, outputs):
                    self._key_cache.pop(rid, None)
                    self._finalize_retired(rid)
                delivered += 1
        except BaseException:
            # LIFO unwind: the staged (newest) reservation first, then the
            # undelivered remainder of the crashed plan.
            if self._staged is not None:
                staged_plan, _ = self._staged
                self._staged = None
                self.scheduler.release(staged_plan)
            residual = [tick for sp in subplans[delivered:]
                        for tick in sp.ticks]
            if residual:
                self.scheduler.release(SlotPlan(
                    plan.signature, plan.slots, residual, reserved=True,
                    group=plan.group))
            raise
        return plan.n_ticks

    def tick(self) -> bool:
        """Serve one dispatch (up to ``ticks_per_dispatch`` ticks in one host
        round trip); return False when idle."""
        return self._dispatch_next(self.cfg.ticks_per_dispatch) > 0

    def run(self, max_ticks: int = 10_000) -> Dict[int, SampleResult]:
        """Serve until the queue drains (or ``max_ticks`` ticks ran).

        Idle states — an empty queue, or one holding only cancelled
        requests — return immediately with whatever ``done`` already holds;
        they can never spin the tick budget."""
        served = 0
        while served < max_ticks:
            n = self._dispatch_next(max_ticks - served)
            if n == 0:
                return self.done
            served += n
        if self.pending():
            raise RuntimeError(
                f"max_ticks={max_ticks} exhausted with {len(self.pending())} "
                "request(s) still queued; raise max_ticks or slots"
            )
        return self.done
