"""Device-side serving executor: one jit'd multi-tick dispatch per signature.

The executor is the device half of the SDE serving core (the host half is
:mod:`repro.serving.scheduler`).  It knows nothing about requests or queues:
its unit of work is a **tick stack** — a ``(n_ticks, slots)`` buffer of
per-path PRNG keys, all ticks sharing one request signature — which it runs
through :func:`repro.core.sdeint_ticks`: an on-device ``lax.map`` over the
tick axis inside ONE jit'd dispatch.  A deep queue therefore
costs one host round trip per signature *stack* instead of one per tick;
``n_dispatches`` / ``n_ticks`` counters expose the ratio (the
``bench_serving`` metric).

Executables are cached per ``(signature, n_ticks)`` — the engine dispatches
only full ``ticks_per_dispatch`` stacks plus single ticks (shallow queue
tails are served tick-by-tick rather than as fresh depths), so a serving
loop that drains a deep queue touches at most two entries per signature
and never recompiles on a varying tail.  The key stack is not donated: no
output has its ``uint32`` shape, so XLA could not reuse its buffer on any
backend (jax would only warn that the donation was unusable).

When the executor is built with a ``mesh_axis``, every tick's ``slots`` axis
is sharded over that device-mesh axis through ``sdeint``'s existing
``shard_map`` fan-out — ``slots = devices x per_device_slots`` becomes the
serving unit — while the tick axis stays sequential (ticks are serving time,
not parallel work).  Path keys are placement-independent
(``fold_in(seed, i)``), so sharded, multi-tick, and single-tick dispatch all
produce bitwise-identical samples.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import parse_solver_spec, sdeint_ticks

from .bucketing import BucketKey

__all__ = ["TickExecutor"]


class TickExecutor:
    """Run same-signature tick stacks for one SDE term on one (set of)
    device(s).  ``term``/``y0``/``args`` define the process; ``mesh`` +
    ``mesh_axis`` optionally shard each tick's slot axis."""

    def __init__(self, term, y0, *, args: Any = None, noise_shape=None,
                 dtype: Any = jnp.float32, mesh=None,
                 mesh_axis: Optional[str] = None,
                 guard: Optional[float] = None):
        if (mesh is None) != (mesh_axis is None):
            # Both or neither: a long-lived executor must not resolve the
            # mesh from whatever `with mesh:` context is ambient at dispatch
            # time (and mesh-without-axis has no defined sharding).
            raise ValueError(
                "sharded dispatch needs mesh and mesh_axis together; got "
                f"mesh={'set' if mesh is not None else 'None'}, "
                f"mesh_axis={mesh_axis!r}"
            )
        self.term = term
        self.y0 = y0
        self.args = args
        self.noise_shape = noise_shape
        self.dtype = dtype
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        # In-loop blow-up guard threshold: every executable carries the
        # per-path divergence check (see repro.core.adjoint.solve) and its
        # results gain a (n_ticks, slots) bool ``diverged`` leaf.  The flag
        # stays on device until the scheduler retires the request — no per-
        # dispatch host sync.  None compiles guard-free executables.
        self.guard = guard
        self._compiled: Dict[Tuple, Any] = {}
        # Host-round-trip accounting: n_dispatches counts jit re-entries
        # (host -> device round trips), n_ticks the engine ticks they served.
        self.n_dispatches = 0
        self.n_ticks = 0

    def _stack_fn(self, key: Union[Tuple, BucketKey], n_ticks: int):
        """The cached jit'd dispatch for ``(key, n_ticks)``.

        ``key`` is either an exact request signature (the classic path) or a
        :class:`~repro.serving.bucketing.BucketKey`, whose executable
        integrates the padded grid and takes a per-tick ``active_steps``
        operand as its second argument.

        Steady-state serving re-enters the same executable every dispatch
        (no per-tick re-jit: the cache key is the signature-or-bucket plus
        the stack depth, and the scheduler canonicalises specs at submit so
        equivalent spellings share an entry).
        """
        cache_key = (key, n_ticks)
        if cache_key not in self._compiled:
            if isinstance(key, BucketKey):
                bk = key

                def stack(tick_keys, active_steps):
                    return sdeint_ticks(
                        self.term, bk.solver, bk.t0,
                        bk.t0 + bk.n_padded * bk.h, bk.n_padded, self.y0,
                        tick_keys, active_steps=active_steps,
                        step_size=bk.h, args=self.args,
                        noise_shape=self.noise_shape, dtype=self.dtype,
                        mesh=self.mesh, mesh_axis=self.mesh_axis,
                        guard=self.guard,
                    )
            else:
                solver, t0, t1, n_steps, save_every, rtol, atol, save_at = key
                extra = {}
                if rtol is not None:
                    extra["rtol"] = rtol
                if atol is not None:
                    extra["atol"] = atol
                if save_at is not None:
                    extra["save_at"] = jnp.asarray(save_at)

                if parse_solver_spec(solver)[1].get("adaptive", False):
                    # Serving is forward-only: the while-loop stepper stops
                    # when every path reaches t1 instead of padding to the
                    # n_steps budget (bitwise-identical results).
                    extra["bounded"] = False

                def stack(tick_keys):
                    return sdeint_ticks(
                        self.term, solver, t0, t1, n_steps, self.y0,
                        tick_keys, args=self.args, save_every=save_every,
                        noise_shape=self.noise_shape, dtype=self.dtype,
                        mesh=self.mesh, mesh_axis=self.mesh_axis,
                        guard=self.guard, **extra,
                    )

            self._compiled[cache_key] = jax.jit(stack)
        return self._compiled[cache_key]

    def has_compiled(self, key: Union[Tuple, BucketKey],
                     n_ticks: int) -> bool:
        """Whether a ``dispatch(key, <n_ticks-deep stack>)`` will re-enter a
        cached executable.  False means the call pays tracing + XLA compile —
        the async engine runs such first dispatches in a worker thread so
        the event loop (other submitters/awaiters) stays responsive."""
        return (key, n_ticks) in self._compiled

    def warmup(self, key: Union[Tuple, BucketKey], n_ticks: int,
               slots: int) -> bool:
        """Ahead-of-time compile the ``(key, n_ticks)`` executable.

        Uses jit's ``lower(...).compile()`` AOT path on shape/dtype structs,
        so no device work runs and no keys are materialised; the compiled
        object is stored back in the cache (its call syntax matches the jit
        wrapper's).  With a persistent compile cache enabled this both
        populates and reads the on-disk cache.  Returns True when this call
        actually lowered+compiled (False: the entry was already compiled).
        """
        fn = self._stack_fn(key, n_ticks)
        if not hasattr(fn, "lower"):  # already AOT-compiled earlier
            return False
        keys_t = jax.ShapeDtypeStruct((n_ticks, slots, 2), jnp.uint32)
        if isinstance(key, BucketKey):
            active_t = jax.ShapeDtypeStruct((n_ticks,), jnp.int32)
            compiled = fn.lower(keys_t, active_t).compile()
        else:
            compiled = fn.lower(keys_t).compile()
        self._compiled[(key, n_ticks)] = compiled
        return True

    def dispatch(self, key: Union[Tuple, BucketKey], tick_keys,
                 active_steps=None):
        """Run a ``(n_ticks, slots, ...)`` key stack; one host round trip.

        For a :class:`BucketKey`, ``active_steps`` (shape ``(n_ticks,)``
        int32 — each tick's true step count) is forwarded as the bucket
        executable's second operand; exact signatures take keys only.

        Returns the solve result pytree with leading ``(n_ticks, slots)``
        axes on every leaf; tick ``t`` is bitwise equal to a single-tick
        dispatch of ``tick_keys[t]`` (see :func:`repro.core.sdeint_ticks`).
        """
        n_ticks = tick_keys.shape[0]
        fn = self._stack_fn(key, n_ticks)
        if isinstance(key, BucketKey):
            if active_steps is None:
                raise ValueError("bucketed dispatch needs active_steps")
            out = fn(tick_keys, jnp.asarray(active_steps, jnp.int32))
        else:
            out = fn(tick_keys)
        self.n_dispatches += 1
        self.n_ticks += n_ticks
        return out
