"""Execution-backend registry for :class:`repro.dist.GraphOperator`.

A backend is a builder ``build(op, *, mesh=None, partition=None, **options)
-> ExecutionPlan``.  Registering is decoupled from dispatch so new execution
strategies (gossip-averaged application, BCSR SpMV variants, async halo, ...)
plug in without touching any caller:

    from repro.dist.backends import register_backend

    @register_backend("my-backend")
    def build(op, *, mesh=None, partition=None, **options):
        ...
        return ExecutionPlan(op=op, backend="my-backend", ...)

The built-in backends each describe a local form (`repro.dist.operator.
LocalForm`: the local matvec record, the recurrence, how signals enter
and leave a shard) and get their plan from the one plan body,
`repro.dist.operator.plan_from_form`.

Built-in backends (imported at the bottom so their decorators run):
  dense       — matvec against P as given (dense matrix or closure)
  pallas      — Block-ELL SpMV + fused Chebyshev-step Pallas kernels
  halo        — shard_map, ring halo exchange of boundary blocks (banded P)
  pallas_halo — shard_map, per-shard Block-ELL fused kernels, boundary-rows-
                only halo exchange (banded P; the sharded hot path)
  allgather   — shard_map, all_gather of the iterate (general P)
"""
from __future__ import annotations

from typing import Callable, Dict, List

_REGISTRY: Dict[str, Callable] = {}


def register_backend(name: str) -> Callable:
    """Decorator: register an ExecutionPlan builder under `name`."""

    def deco(build: Callable) -> Callable:
        _REGISTRY[name] = build
        return build

    return deco


def get_backend(name: str) -> Callable:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown execution backend {name!r}; "
            f"available: {available_backends()}") from None


def available_backends() -> List[str]:
    return sorted(_REGISTRY)


# Import order matters only in that halo must precede pallas_halo (which
# reuses halo's banded partition).  Each import registers its builder.
from . import dense        # noqa: E402,F401
from . import pallas       # noqa: E402,F401
from . import halo         # noqa: E402,F401
from . import pallas_halo  # noqa: E402,F401
from . import allgather    # noqa: E402,F401
