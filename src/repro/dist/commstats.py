"""Communication accounting for execution plans (Section IV-B/C made
measurable).

The paper's headline systems claim is that one distributed application of a
union of M graph multipliers of order K costs ``2K|E|`` messages — per
Chebyshev order, every vertex sends one scalar to every neighbour, and the
count scales with the edge set only (Section IV-B; 4K|E| for the Gram
operator, length-eta messages for the adjoint).  This module *measures*
what a compiled plan actually does instead of trusting the closed form:

  * :func:`measure` traces a plan method to its jaxpr and tallies every
    collective primitive it will execute — ``ppermute``, ``all_gather``,
    ``psum``, ... — walking nested jaxprs (scan bodies are multiplied by
    their trip count, so a K-order recurrence reports K matvec exchanges,
    not one).
  * :class:`CommStats` converts the tally into the two accountings used
    throughout the repo:
      - **device level** — collectives / bytes actually crossing the mesh
        per application (what `plan.info`'s ``*_bytes_per_apply`` models;
        both halo backends ship only the h-row boundary tile per direction
        per order — :attr:`CommStats.bytes_per_round` exposes it);
      - **paper level** — :meth:`CommStats.paper_messages`, the sensor-
        network message count ``rounds x 2|E|`` where `rounds` is the
        measured number of neighbour-exchange rounds.  For a faithful
        Algorithm 1 implementation ``rounds == K`` and the measured count
        equals the ``2K|E|`` prediction of
        :meth:`repro.core.multiplier.UnionMultiplier.message_counts`.
  * :func:`plan_comm_stats` runs the measurement over a plan's
    apply / apply_adjoint / apply_gram in one call; ``batch=B`` traces the
    batched (B, N) signatures of the (..., N) contract, and
    :meth:`CommStats.paper_messages_per_signal` reports the amortized
    2K|E|/B count (total rounds are batch-invariant —
    :func:`verify_message_scaling` asserts it).

``benchmarks/bench_scaling.py`` sweeps this over growing sensor graphs to
emit the communication-vs-network-size curve, and
``tests/test_commstats.py`` pins the closed form on known graphs.

Caveats: counts are static (trace-time) quantities.  Backends that skip
collectives on a 1-shard mesh (halo / pallas_halo guard ``size > 1``)
measure zero there — measure on >= 2 shards.  A collective under a
`while` body has *no* static count (the trip count is unknown at trace
time), so :func:`measure` refuses to undercount it: it raises by default
(``while_loops="error"``; pass ``"warn"`` to tally one trip loudly
instead).  The jaxpr traversal itself lives in
:mod:`repro.analysis.jaxpr_walk` (extracted from this module's original
private walker), where `repro.analysis.checks` reuses it for the static
invariant checks (`JX-COLLECTIVE-IN-WHILE` is this same rule, CI-gated).
"""
from __future__ import annotations

import dataclasses
import logging
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

# The shared jaxpr visitor (Layer-1 substrate of `repro.analysis`); this
# module re-exports COLLECTIVE_PRIMITIVES from it for compatibility.
from .. import obs
from ..analysis.jaxpr_walk import (COLLECTIVE_PRIMITIVES, eqn_payload,
                                   walk_jaxpr)

logger = logging.getLogger(__name__)

#: The scope of a general plan's moves between vertex and partition order
#: (`partition.sharded_reorder`): not an exchange round.
REORDER_SCOPE = obs.PREFIX + "reorder"


@dataclasses.dataclass(frozen=True)
class CollectiveCall:
    """One collective site, aggregated over loop trips.

    count: executions per plan application (per shard);
    elems / nbytes: payload per shard per execution;
    perm: for ppermute, the (src, dst) permutation as a tuple of pairs —
    distinct perms are distinct exchange directions (`exchange_rounds`
    groups by it when the plan does not declare its own divisor).
    """

    primitive: str
    count: int
    elems: int
    nbytes: int
    perm: Optional[Tuple] = None


@dataclasses.dataclass(frozen=True)
class CommStats:
    """Measured communication of one traced function (one plan method).

    `batch` is the number of signals the traced call processed at once
    (the leading batch size of the (..., N) contract); exchange *rounds*
    are batch-invariant — the recurrence is linear, so B signals share the
    K rounds — and the per-signal accessors divide the paper-level message
    count by `batch` to expose the amortization (2K|E|/B per signal).
    """

    collectives: Tuple[CollectiveCall, ...]
    n_shards: int
    batch: int = 1
    ppermutes_per_round: Optional[int] = None
    #: collectives under the ``repro.reorder`` scope (a general plan moving
    #: signals between vertex and partition order): tallied apart, never
    #: exchange rounds or their bytes
    reorder: Tuple[CollectiveCall, ...] = ()

    @property
    def n_collectives(self) -> int:
        """Total collective executions per application (per shard)."""
        return sum(c.count for c in self.collectives)

    @property
    def exchange_rounds(self) -> int:
        """Neighbour-exchange rounds == matvec applications of P.

        The banded ring backends issue one ppermute *pair* per matvec
        (halo / pallas_halo), a `GeneralPartition` plan issues one
        ppermute per active ring offset per matvec, and allgather issues
        one all_gather per matvec; everything else (psum, ...) is not a
        recurrence round.  Resolution order: the plan-declared divisor
        (`ppermutes_per_round`, from plan.info's
        ``exchange_collectives_per_round`` — authoritative, since e.g. at
        S=2 the two ring directions share one perm and perm-grouping alone
        would halve the count), then the max per-perm tally (each matvec
        touches every exchange direction once), then the legacy pair
        assumption.
        """
        pp = sum(c.count for c in self.collectives
                 if c.primitive == "ppermute")
        ag = sum(c.count for c in self.collectives
                 if c.primitive in ("all_gather", "pgather"))
        if self.ppermutes_per_round:
            return pp // self.ppermutes_per_round + ag
        if pp:
            by_perm: Dict[Any, int] = {}
            for c in self.collectives:
                if c.primitive == "ppermute":
                    by_perm[c.perm] = by_perm.get(c.perm, 0) + c.count
            if None not in by_perm:
                return max(by_perm.values()) + ag
        return pp // 2 + ag

    @property
    def bytes_per_shard(self) -> int:
        """Payload bytes one shard sends per application."""
        return sum(c.count * c.nbytes for c in self.collectives)

    @property
    def reorder_bytes_per_shard(self) -> int:
        """Payload bytes one shard sends moving signals between vertex
        and partition order, per application."""
        return sum(c.count * c.nbytes for c in self.reorder)

    @property
    def bytes_per_round(self) -> float:
        """Average payload bytes one shard ships per exchange round.

        The device-level view of the interior/boundary split: the halo
        backends should measure ``2 * h * dtype_bytes`` here (both
        directions of one boundary-tile exchange, h = coupling bandwidth)
        regardless of K — the per-order payload is what shrank, the round
        count (the paper-level accounting) is untouched.
        """
        r = self.exchange_rounds
        return self.bytes_per_shard / r if r else 0.0

    @property
    def total_bytes(self) -> int:
        """Payload bytes crossing the mesh per application (all shards)."""
        return self.bytes_per_shard * self.n_shards

    def paper_messages(self, n_edges: int) -> int:
        """Sensor-network message count: measured rounds x 2|E| scalars.

        In the paper's fully distributed model every matvec (= exchange
        round) moves one scalar along each *directed* edge, so a plan that
        really implements Algorithm 1 at order K measures exactly the
        predicted ``2K|E|`` of `op.message_counts(n_edges)`.  This is the
        *total* for the whole batched application; see
        :meth:`paper_messages_per_signal` for the amortized view.
        """
        return self.exchange_rounds * 2 * n_edges

    def paper_messages_per_signal(self, n_edges: int) -> float:
        """Amortized message count per signal: 2K|E| / batch.

        The batch shares the K rounds, so B-batched execution costs each
        signal a 1/B share of the paper's message bound — the quantity
        :func:`verify_message_scaling` asserts against the closed form.
        """
        return self.paper_messages(n_edges) / self.batch

    def summary(self) -> Dict[str, Any]:
        return {
            "n_shards": self.n_shards,
            "batch": self.batch,
            "n_collectives": self.n_collectives,
            "exchange_rounds": self.exchange_rounds,
            "bytes_per_shard": self.bytes_per_shard,
            "total_bytes": self.total_bytes,
            "collectives": [dataclasses.asdict(c) for c in self.collectives],
        }


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------
class UncountableCollectiveError(RuntimeError):
    """A collective sits under a `while_loop`: its execution count is not a
    static (trace-time) quantity, so any tally would be wrong.  Restructure
    the loop as a `scan` (fixed trip count) or measure the bounded inner
    function directly."""


def measure(fn: Callable, *example_args, n_shards: int = 1,
            batch: int = 1, while_loops: str = "error",
            ppermutes_per_round: Optional[int] = None) -> CommStats:
    """Trace `fn` on example arguments and tally its collectives.

    `example_args` may be concrete arrays or `jax.ShapeDtypeStruct`s —
    tracing is abstract, nothing is executed on devices.  `n_shards` scales
    the per-shard byte counts to mesh totals (pass the plan's shard count);
    `batch` records how many signals the traced call carries so the
    per-signal accessors can amortize.

    A collective under a ``while_loop`` executes once per trip of a count
    unknown at trace time — no static tally is correct.
    ``while_loops="error"`` (default) raises
    :class:`UncountableCollectiveError`; ``"warn"`` emits a `UserWarning`
    (+ WARNING log) and counts the site once per enclosing-scan trip, so
    the returned stats are an explicit *lower bound*.

    Collectives under the ``repro.reorder`` scope (a general plan moving
    its signals between vertex and partition order) are tallied apart, in
    :attr:`CommStats.reorder`: they are not exchange rounds.

    `ppermutes_per_round` forwards a plan-declared
    ``exchange_collectives_per_round`` to :attr:`CommStats.exchange_rounds`
    (how many ppermutes one neighbour-exchange round comprises: 2 for the
    banded ring, the number of active ring offsets for a
    `GeneralPartition`).
    """
    if while_loops not in ("error", "warn"):
        raise ValueError(
            f"while_loops must be 'error' or 'warn', got {while_loops!r}")
    closed = jax.make_jaxpr(fn)(*example_args)
    tally: Dict[Tuple[str, int, int, Any], int] = {}
    reorder: Dict[Tuple[str, int, int, Any], int] = {}

    def visit(eqn, ctx):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMITIVES:
            return
        into = (reorder if REORDER_SCOPE in
                str(eqn.source_info.name_stack).split("/") else tally)
        if ctx.in_while:
            msg = (
                f"collective `{name}` under a while_loop (path "
                f"{'/'.join(ctx.path) or '<top>'}): trip count is unknown "
                "at trace time, so no static tally is correct")
            if while_loops == "error":
                raise UncountableCollectiveError(msg)
            warnings.warn(msg + " — counting ONE trip; stats are a lower "
                          "bound", stacklevel=3)
            logger.warning("commstats.measure: %s (counting one trip)", msg)
        elems, nbytes = eqn_payload(eqn)
        perm = eqn.params.get("perm") if name == "ppermute" else None
        if perm is not None:
            perm = tuple(tuple(int(v) for v in p) for p in perm)
        key = (name, elems, nbytes, perm)
        into[key] = into.get(key, 0) + ctx.mult

    walk_jaxpr(closed, visit)

    def calls(t):
        return tuple(
            CollectiveCall(primitive=k[0], count=v, elems=k[1], nbytes=k[2],
                           perm=k[3])
            for k, v in sorted(t.items(),
                               key=lambda kv: (kv[0][:3], repr(kv[0][3]))))

    return CommStats(collectives=calls(tally), n_shards=n_shards,
                     batch=batch, ppermutes_per_round=ppermutes_per_round,
                     reorder=calls(reorder))


def plan_comm_stats(plan, n: int = None, batch: int = None) -> Dict[str, CommStats]:
    """Measure a plan's apply / apply_adjoint / apply_gram communication.

    `n` (logical signal size) defaults to the operator's dense-P dimension;
    pass it explicitly for closure-P operators.  `batch=None` traces the
    unbatched (N,) signatures; `batch=B` traces (B, N) / (B, eta, N) ones
    (the (..., N) contract) and stamps B on the returned stats so
    `paper_messages_per_signal` reports the 2K|E|/B amortization.  Returns
    ``{"apply": CommStats, "apply_adjoint": ..., "apply_gram": ...}``.
    """
    op = plan.op
    if n is None:
        if callable(op.P):
            raise ValueError("plan_comm_stats needs n= for a closure P")
        n = int(np.asarray(op.P).shape[0])
    shards = int(plan.info.get("n_shards", 1))
    ppr = plan.info.get("exchange_collectives_per_round")
    lead = () if batch is None else (int(batch),)
    b = 1 if batch is None else int(batch)
    f = jax.ShapeDtypeStruct(lead + (n,), np.float32)
    a = jax.ShapeDtypeStruct(lead + (op.eta, n), np.float32)
    return {
        "apply": measure(plan.apply, f, n_shards=shards, batch=b,
                         ppermutes_per_round=ppr),
        "apply_adjoint": measure(plan.apply_adjoint, a, n_shards=shards,
                                 batch=b, ppermutes_per_round=ppr),
        "apply_gram": measure(plan.apply_gram, f, n_shards=shards, batch=b,
                              ppermutes_per_round=ppr),
    }


def solve_comm_stats(plan, method: str = "chebyshev", n: int = None,
                     batch: int = None, **solve_kwargs) -> CommStats:
    """Measure the communication of one `plan.solve(method=...)` call.

    Traces ``plan.solve(y, method, **solve_kwargs).x`` on a (batch, n) (or
    unbatched (n,)) float32 signal and tallies its collectives — the
    Section-V accounting made measurable: a Jacobi round on
    den(P) x = num(P) y costs deg(den) matvec exchanges (Fig. 2(b)'s "2
    matvecs per iteration" shows up as ``exchange_rounds == 2 * n_iters``),
    the ARMA recursion's stacked poles cost ONE exchange of length-K_p
    messages per round, and batched signals leave the round count invariant
    (`SolveResult.info["exchange_rounds"]` is the closed form this should
    land on exactly).  Backends skip collectives on 1-shard meshes —
    measure on >= 2 shards, like :func:`plan_comm_stats`.
    """
    op = plan.op
    if n is None:
        if callable(op.P):
            raise ValueError("solve_comm_stats needs n= for a closure P")
        n = int(np.asarray(op.P).shape[0])
    shards = int(plan.info.get("n_shards", 1))
    ppr = plan.info.get("exchange_collectives_per_round")
    lead = () if batch is None else (int(batch),)
    b = 1 if batch is None else int(batch)
    y = jax.ShapeDtypeStruct(lead + (n,), np.float32)

    def run(sig):
        return plan.solve(sig, method, **solve_kwargs).x

    return measure(run, y, n_shards=shards, batch=b,
                   ppermutes_per_round=ppr)


def verify_message_scaling(plan, n_edges: int, n: int = None,
                           batch: int = None) -> Dict[str, Any]:
    """Measured-vs-predicted message counts for one plan.

    Compares :meth:`CommStats.paper_messages` for each plan method against
    the closed forms of `op.message_counts(n_edges)` (2K|E| apply, 2K|E|
    adjoint, 4K|E| gram).  Returns a dict with measured, predicted and the
    max relative deviation — the quantity `bench_scaling.py` asserts is
    within 10%.

    With `batch=B` the batched signatures are traced as well and the
    exchange-round counts are *asserted* batch-invariant (the tentpole
    claim: B signals share the K rounds, so per-signal messages are
    2K|E|/B).  The result then carries ``batch``, ``measured_batched``
    (total rounds at B — must equal the unbatched totals) and
    ``per_signal_messages`` (the amortized counts).
    """
    stats = plan_comm_stats(plan, n=n)
    predicted = plan.op.message_counts(n_edges)
    pred = {
        "apply": predicted["apply_messages"],
        "apply_adjoint": predicted["adjoint_messages"],
        "apply_gram": predicted["gram_messages"],
    }
    meas = {k: s.paper_messages(n_edges) for k, s in stats.items()}
    rel = {
        k: (abs(meas[k] - pred[k]) / pred[k]) if pred[k] else 0.0
        for k in pred
    }
    out = {
        "measured": meas,
        "predicted": pred,
        "rel_dev": rel,
        "max_rel_dev": max(rel.values()),
        "stats": {k: s.summary() for k, s in stats.items()},
    }
    if batch is not None:
        bstats = plan_comm_stats(plan, n=n, batch=batch)
        for k in stats:
            r1, rb = stats[k].exchange_rounds, bstats[k].exchange_rounds
            if r1 != rb:
                raise AssertionError(
                    f"{plan.backend}.{k}: exchange rounds are not batch-"
                    f"invariant ({r1} at B=1 vs {rb} at B={batch}) — the "
                    "batched path is re-running the recurrence per signal")
        out["batch"] = int(batch)
        out["measured_batched"] = {
            k: s.paper_messages(n_edges) for k, s in bstats.items()}
        out["per_signal_messages"] = {
            k: s.paper_messages_per_signal(n_edges)
            for k, s in bstats.items()}
        out["stats_batched"] = {k: s.summary() for k, s in bstats.items()}
    return out
