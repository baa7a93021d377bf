"""repro.serving — batched engines.

  engine       — LM continuous-batching decode engine (fixed-slot serve_step)
  scheduler    — host-side SDE serving core: priority/FIFO queue, signature
                 grouping, slot plans, admission control, result
                 scatter/retirement (device-free)
  executor     — device-side SDE serving core: jit'd on-device multi-tick
                 dispatch, optional mesh-sharded slot axis
  bucketing    — signature coalescing: padded bucketed dispatch (ladder
                 rungs + BucketKey planning groups, bitwise-identical)
  sde_engine   — Monte-Carlo SDE sampling engine (façade over the two layers)
  async_engine — asyncio continuous-batching serving plane: awaitable
                 submit/result with backpressure, cross-signature
                 interleaving, host-side double buffering, device-resident
                 results
  faults       — deterministic fault injection (NaN trajectories, transient
                 executor crashes, delays) + FakeClock, for exercising the
                 robustness layer (guards, retries, deadlines, restarts)
"""
from .async_engine import AsyncSDESampleEngine
from .bucketing import BucketingConfig, BucketKey, bucket_key, group_key, ladder_rung
from .engine import Engine, ServeConfig
from .executor import TickExecutor
from .faults import FakeClock, FaultConfig, FaultyExecutor, InjectedCrash, inject_faults
from .scheduler import QueueFull, RetryPolicy, Scheduler, SlotPlan
from .sde_engine import SampleRequest, SampleResult, SDESampleConfig, SDESampleEngine

__all__ = [
    "Engine",
    "ServeConfig",
    "QueueFull",
    "Scheduler",
    "SlotPlan",
    "TickExecutor",
    "BucketingConfig",
    "BucketKey",
    "bucket_key",
    "group_key",
    "ladder_rung",
    "AsyncSDESampleEngine",
    "SDESampleEngine",
    "SDESampleConfig",
    "SampleRequest",
    "SampleResult",
    "RetryPolicy",
    "FaultConfig",
    "FaultyExecutor",
    "FakeClock",
    "InjectedCrash",
    "inject_faults",
]
