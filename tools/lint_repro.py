#!/usr/bin/env python3
"""The repo's one lint entry point (CI `lint` job): `repro.analysis` CLI.

Runs up to three layers and applies `tools/lint_allowlist.txt`:

* ``ast``   — the repo-specific AST rules of `repro.analysis.astlint`
  (``RP-*``: dense materialization, order loops, host syncs, unlogged
  fallbacks, legacy-scaffold imports) over `src/repro`, plus the
  tracked-bytecode guard (``RP-TRACKED-BYTECODE``, folded in from the old
  CI `docs` job grep).
* ``jaxpr`` — the trace-level invariant checks of `repro.analysis.checks`
  (``JX-*``: ppermute bijection / deadlock-freedom, no collectives under
  while_loop, B=1 vs B=64 collective-schedule equality, pallas_call VMEM
  budgets, f64 / promotion discipline) over every registered execution
  backend on a bandwidth-1 path graph.  ``--shards 1,8`` runs the sharded
  meshes too: each extra shard count re-execs this script in a subprocess
  with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the
  `tests/_subproc.py` idiom — the parent process stays single-device).
* ``docs``  — `tools/check_docs.py`'s link/coverage checks, reported as
  ``DOC-*`` findings so everything funnels through one allowlist and one
  exit code.

``--check`` exits nonzero on any non-allowlisted finding.  Stale allowlist
entries (matching nothing) are reported as warnings so audit records get
pruned.  Rule catalogue: docs/ARCHITECTURE.md, "Static invariants".
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
from typing import List

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(REPO, "src")
ALLOWLIST = os.path.join(REPO, "tools", "lint_allowlist.txt")

if SRC not in sys.path:
    sys.path.insert(0, SRC)

#: Graph the jaxpr layer traces every backend on: a path graph is banded
#: with coupling bandwidth exactly 1, so every backend (including both halo
#: variants) builds on any contiguous shard split, and the 2K|E| schedule
#: is known in closed form.
LINT_N, LINT_K, LINT_J = 64, 10, 2
LINT_BATCHES = (1, 64)
MESH_AXIS = "graph"
#: Backends that take a mesh (the rest are single-device).
SHARDED_BACKENDS = ("halo", "pallas_halo", "allgather")


def ast_findings(allowlist) -> List:
    from repro.analysis import Finding, lint_tree

    # main() chdirs to the repo root, so paths come out repo-relative —
    # the form the allowlist and REF_PATHS match against
    findings = lint_tree("src/repro", src_root="src",
                         scaffold_globs=allowlist.scaffold_globs)
    # tracked-bytecode guard (was a raw grep in the CI docs job)
    try:
        tracked = subprocess.run(
            ["git", "ls-files"], cwd=REPO, capture_output=True, text=True,
            check=True).stdout.splitlines()
    except (OSError, subprocess.CalledProcessError):
        tracked = []
    for path in tracked:
        if "__pycache__/" in path or path.endswith((".pyc", ".pyo",
                                                    ".pyd")):
            findings.append(Finding(
                rule="RP-TRACKED-BYTECODE", path=path,
                message="Python bytecode is tracked by git — it churns "
                        "every PR and leaks local paths; git rm it "
                        "(__pycache__/ and *.pyc are gitignored)"))
    return findings


def docs_findings() -> List:
    from repro.analysis import Finding

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import check_docs

    findings = []
    for path, target, resolved in check_docs.broken_links():
        findings.append(Finding(
            rule="DOC-LINK", path=path,
            message=f"broken link ({target}) -> {resolved}"))
    for name in check_docs.undocumented_backends():
        findings.append(Finding(
            rule="DOC-BACKEND-ARCH", path="docs/ARCHITECTURE.md",
            message=f"backend {name!r} is registered but not documented"))
    for name in check_docs.undocumented_backends_api():
        findings.append(Finding(
            rule="DOC-BACKEND-API", path="API.md",
            message=f"backend {name!r} is registered but missing"))
    for name in check_docs.undocumented_solve_methods():
        findings.append(Finding(
            rule="DOC-SOLVE-METHOD", path="API.md",
            message=f"plan.solve method {name!r} is not documented"))
    return findings


def _lint_operator():
    import jax

    from repro.core import graph, wavelets
    from repro.dist import GraphOperator

    g = graph.path_graph(LINT_N)
    lmax = g.lambda_max_bound()
    return GraphOperator(
        P=g.laplacian(),
        multipliers=wavelets.sgwt_multipliers(lmax, J=LINT_J),
        lmax=lmax, K=LINT_K)


def _lint_community_operator():
    """A small non-banded community graph: the GeneralPartition matrix's
    operator (the banded `_lint_operator` would reduce to the ring plan)."""
    import numpy as np

    from repro.core import wavelets
    from repro.dist import GraphOperator
    from repro.dist.partition import community_graph_csr

    csr, meta = community_graph_csr(64, n_communities=8, seed=0)
    lmax = meta["lmax"]
    return GraphOperator(
        P=np.asarray(csr.to_dense()),
        multipliers=wavelets.sgwt_multipliers(lmax, J=LINT_J),
        lmax=lmax, K=LINT_K)


#: The fault configuration the JX-FAULT-NO-EXTRA-COLLECTIVES gate traces
#: (all three channels firing, hold_last for the stateful carried tiles —
#: the config with the most machinery that could accidentally add rounds).
LINT_FAULT_SPEC = {"drop_prob": 0.1, "stale_prob": 0.1, "noise_prob": 0.1,
                   "seed": 0}


def jaxpr_findings(shards: int) -> List:
    import jax

    from repro.analysis import check_fault_schedule, check_plan
    from repro.dist.backends import available_backends

    n_dev = jax.device_count()
    if shards > n_dev:
        raise SystemExit(
            f"jaxpr layer needs {shards} devices, have {n_dev} — run via "
            f"--shards (the CLI sets XLA_FLAGS in a subprocess) instead "
            "of calling the inner layer directly")
    op = _lint_operator()
    mesh = jax.make_mesh((shards,), (MESH_AXIS,))
    findings = []
    for backend in available_backends():
        if backend in SHARDED_BACKENDS:
            plan = op.plan(backend, mesh=mesh)
        elif shards > 1:
            continue  # single-device backends are covered at shards=1
        else:
            plan = op.plan(backend)
        findings += check_plan(
            plan, batches=LINT_BATCHES,
            budget=plan.info.get("sweep_vmem_budget"),
            solve_methods=("jacobi",))
        if backend in ("halo", "pallas_halo"):
            faulted = op.plan(backend, mesh=mesh, exchange_dtype="int8",
                              fault_spec=LINT_FAULT_SPEC,
                              degradation="hold_last")
            findings += check_fault_schedule(
                op.plan(backend, mesh=mesh, exchange_dtype="int8"),
                faulted, solve_methods=("jacobi",))
    # GeneralPartition matrix: the same invariants (JX-PPERMUTE-BIJECTION
    # in particular — the multi-offset exchange realizes each round as
    # complete ppermute bijections) on a non-banded community graph.
    community_op = _lint_community_operator()
    for backend in ("halo", "pallas_halo"):
        if backend not in available_backends():
            continue
        plan = community_op.plan(backend, mesh=mesh, partition="general")
        findings += check_plan(
            plan, batches=LINT_BATCHES,
            budget=plan.info.get("sweep_vmem_budget"),
            solve_methods=("jacobi",))
        findings += check_fault_schedule(
            plan,
            community_op.plan(backend, mesh=mesh, partition="general",
                              fault_spec=LINT_FAULT_SPEC,
                              degradation="hold_last"),
            solve_methods=("jacobi",))
    return findings


def _spawn_sharded(shards: int, allowlist_path: str) -> int:
    """Run the jaxpr layer at `shards` host devices in a subprocess."""
    env = dict(os.environ)
    # a CPU trace: the child must never contend for this process's chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={shards} "
        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--check",
         "--layers", "jaxpr", "--inner-shards", str(shards),
         "--allowlist", allowlist_path],
        env=env)
    return proc.returncode


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="repro static analysis (jaxpr invariants + AST lint "
                    "+ docs)")
    parser.add_argument("--check", action="store_true",
                        help="exit nonzero on non-allowlisted findings")
    parser.add_argument("--layers", default="ast,docs,jaxpr",
                        help="comma-set of ast|docs|jaxpr (default: all)")
    parser.add_argument("--shards", default="1,8",
                        help="comma-list of shard counts for the jaxpr "
                             "layer; counts > 1 re-exec in a subprocess "
                             "with forced host devices (default: 1,8)")
    parser.add_argument("--inner-shards", type=int, default=None,
                        help=argparse.SUPPRESS)  # subprocess entry
    parser.add_argument("--allowlist", default=ALLOWLIST)
    args = parser.parse_args(argv)
    os.chdir(REPO)
    layers = [l.strip() for l in args.layers.split(",") if l.strip()]
    unknown = set(layers) - {"ast", "docs", "jaxpr"}
    if unknown:
        parser.error(f"unknown layers: {sorted(unknown)}")

    from repro.analysis import Allowlist, AllowlistError

    try:
        allowlist = Allowlist.load(args.allowlist)
    except FileNotFoundError:
        allowlist = Allowlist()
    except AllowlistError as e:
        print(f"allowlist error: {e}", file=sys.stderr)
        return 2

    findings = []
    if "ast" in layers:
        findings += ast_findings(allowlist)
    if "docs" in layers:
        findings += docs_findings()
    rc = 0
    if "jaxpr" in layers:
        if args.inner_shards is not None:
            findings += jaxpr_findings(args.inner_shards)
        else:
            shard_counts = sorted({int(s) for s in args.shards.split(",")})
            if shard_counts and shard_counts[0] == 1:
                findings += jaxpr_findings(1)
                shard_counts = shard_counts[1:]
            for s in shard_counts:
                sub_rc = _spawn_sharded(s, args.allowlist)
                if sub_rc:
                    print(f"jaxpr layer at {s} shards: FAILED "
                          f"(rc={sub_rc})", file=sys.stderr)
                    rc = max(rc, 1)

    kept, suppressed = allowlist.split(findings)
    for f in kept:
        print(str(f), file=sys.stderr)
    scope = f"layers={','.join(layers)}"
    if args.inner_shards is not None:
        scope += f" shards={args.inner_shards}"
    for entry in (allowlist.unused_entries(findings)
                  if args.inner_shards is None and
                  layers == ["ast", "docs", "jaxpr"] else ()):
        # only a full default run can judge staleness: partial layers
        # legitimately miss entries
        print(f"warning: stale allowlist entry matches nothing: "
              f"{entry.rule} {entry.path_glob}"
              + (f"::{entry.symbol}" if entry.symbol else ""),
              file=sys.stderr)
    print(f"lint_repro [{scope}]: {len(kept)} finding(s), "
          f"{len(suppressed)} allowlisted")
    if kept and args.check:
        rc = max(rc, 1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
