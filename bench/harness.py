"""One run of one cell: set up, drive the window, check, report.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``:

- ``configs/<config>.json`` (the path in the config's ``file``): the
  graph (``graph.kind`` names a generator in ``bench/graphs/``), the
  filter bank, the plan and the chips;
- ``traffic/<traffic>.json``: the closed loop's batch and pool;
- ``metrics/<metric>.py``: ``read(run)`` returns the metric's value, or
  None where the run has nothing to read it from;
- ``checks/<workload>.json``: the limit of each number compared.

The system under test is ``ExecutionPlan.compiled("apply")`` of a
``GraphOperator`` planned as the configuration says.  One caller issues
a call on a (B, N) batch already on the device(s), waits for its result
and issues the next, round-robin over a pool of batches drawn from the
seed.  A uniform sample of the window's results, drawn from the seed, is
compared with the plain reference (``bench/reference.py``) once the
window has closed and the program's state is freed.
"""
import dataclasses
import gc
import importlib
import json
import os
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: Device bytes the sampled results may hold between them.
SAMPLE_BYTES = 2 << 30
MAX_SAMPLES = 8
WARM_CALLS = 2
#: JAX's persistent compilation cache: a fixed directory inside the
#: checkout, since the path is part of the cache key.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _read_json(path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Spec:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    checks: dict


def load_spec(workload, root=ROOT):
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic = _read_json(os.path.join(BENCH, "traffic",
                                      cell["traffic"] + ".json"))
    if (traffic["loop"], traffic["clients"], traffic["entry"]) != (
            "closed", 1, "apply"):
        raise ValueError(f"traffic {cell['traffic']!r}: the harness drives "
                         "one closed-loop caller of plan.apply")

    def mine(metrics):
        return [m for m in metrics if workload in m.get("workloads",
                                                        [workload])]

    return Spec(
        workload=cell,
        config=_read_json(os.path.join(root, config["file"])),
        traffic=traffic,
        end_to_end=mine(bench["end_to_end"]),
        per_layer=mine(bench["per_layer"]),
        checks=_read_json(os.path.join(BENCH, "checks", workload + ".json")))


def seed_key(seed):
    """A JAX key from any whole number (the driver's exceed 32 bits)."""
    import jax

    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.key(int(word))


def chips_for(chips, require_chip=True):
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices[:chips]


def use_compile_cache():
    """Compiled programs go to :data:`CACHE_DIR`, whatever the
    environment names, with no cap on the cache's size: a million-vertex
    plan compiles its graph into the program, larger than a capped cache
    keeps, so a cap would make every run compile it anew."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR  # the program's too
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return CACHE_DIR


def kernels_in(compiled):
    """Names of the Pallas kernels (``tpu_custom_call``) in a compiled
    program, from its HLO text."""
    names = set()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            lhs = line.split("=")[0].split()[-1]
            names.add(lhs.lstrip("%").split(".")[0])
    return sorted(names)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    spec: Spec
    graph: object
    devices: list
    plan: object
    sharding: object
    batch: int
    facts: dict

    @property
    def operator(self):
        """The configuration's filter bank: K and J."""
        return self.spec.config["operator"]


def build_cell(spec, devices, plan_overrides=None):
    """The graph, the operator and the plan, on `devices`."""
    import jax
    from jax.sharding import (AxisType, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from bench.graphs import load
    from repro.core import wavelets
    from repro.dist import GraphOperator
    from repro.dist.partition import CSRMatrix, csr_matvec_fn, \
        partition_general

    cfg = spec.config
    t = time.perf_counter()
    graph = load(cfg["graph"])
    graph_s = time.perf_counter() - t
    K, J = cfg["operator"]["K"], cfg["operator"]["J"]
    plan_cfg = dict(cfg["plan"])
    plan_cfg.update(plan_overrides or {})
    backend = plan_cfg.pop("backend")
    block = tuple(plan_cfg.pop("block"))
    facts = {"N": graph.n, "edges": graph.n_edges, "nnz": graph.nnz,
             "lmax": graph.lmax}
    if plan_cfg.get("partition") == "general":
        csr = CSRMatrix(indptr=graph.indptr, indices=graph.indices,
                        data=graph.data)
        P = csr_matvec_fn(csr)
        mesh = jax.make_mesh((len(devices),), ("graph",),
                             axis_types=(AxisType.Auto,), devices=devices)
        plan_cfg["partition"] = partition_general(csr, len(devices),
                                                  block=block)
        plan_cfg["mesh"] = mesh
        sharding = NamedSharding(mesh, PartitionSpec(None, "graph"))
        parts = plan_cfg["partition"]
        facts.update(block_ell=list(parts.blocks.shape),
                     nnz_blocks=parts.nnz_blocks, edge_cut=parts.edge_cut)
    else:
        P = graph.dense()
        plan_cfg["block"] = block
        sharding = SingleDeviceSharding(devices[0])
    with jax.default_device(devices[0]):
        op = GraphOperator(P=P,
                           multipliers=wavelets.sgwt_multipliers(graph.lmax,
                                                                 J),
                           lmax=graph.lmax, K=K)
        plan = op.plan(backend, **plan_cfg)
    facts.update(eta=op.eta, graph_s=graph_s,
                 plan_s=time.perf_counter() - t - graph_s)
    for key in ("nnz_blocks", "sweep_vmem_bytes", "sweep_vmem_budget",
                "padded_n"):
        if key in plan.info and key not in facts:
            facts[key] = plan.info[key]
    return Cell(spec=spec, graph=graph, devices=devices, plan=plan,
                sharding=sharding, batch=int(spec.traffic["batch"]),
                facts=facts)


def make_pool(cell, seed):
    """`pool` batches of (B, N) normal signals, drawn on the device(s)
    in one call from the seed, in the sharding the plan takes."""
    import jax
    import jax.numpy as jnp

    size, shape = int(cell.spec.traffic["pool"]), (cell.batch, cell.graph.n)

    def draw(key):
        return tuple(jax.random.normal(k, shape, jnp.float32)
                     for k in jax.random.split(key, size))

    pool = jax.jit(draw, out_shardings=(cell.sharding,) * size)(
        seed_key(seed))
    jax.block_until_ready(pool)
    return list(pool)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Window:
    start: float
    end: float
    calls: int
    signals: int
    samples: list  # [(call index, pool index, result)]
    ends: list     # host clock at each call's result

    @property
    def seconds(self):
        return self.end - self.start

    def call_seconds(self):
        """Quartiles and the longest of the calls' durations, each from
        the result before it (so the host's pick and issue count): shows
        whether a slow window is one stall or every call slower."""
        import statistics

        d = np.diff([self.start] + self.ends)
        q = statistics.quantiles(d, n=4) if d.size > 1 else [d[0]] * 3
        return {"q1": float(q[0]), "median": float(q[1]), "q3": float(q[2]),
                "max": float(d.max()), "slowest": int(d.argmax())}


def drive(entry, pool, batch, seconds, keep, rng):
    """The closed loop: issue, wait, pick the next batch, until
    `seconds` have passed.  Keeps a uniform sample of `keep` results
    (reservoir sampling with `rng`)."""
    gc.collect()
    gc.disable()  # no collector pauses in the window; refcounts free arrays
    try:
        return _loop(entry, pool, batch, seconds, keep, rng)
    finally:
        gc.enable()


def _loop(entry, pool, batch, seconds, keep, rng):
    from jax.profiler import TraceAnnotation

    samples, calls, ends = [], 0, []
    start = time.perf_counter()
    while True:
        with TraceAnnotation("bench.pick"):
            j = calls % len(pool)
            x = pool[j]
        with TraceAnnotation("bench.issue"):
            out = entry(x)
        with TraceAnnotation("bench.wait"):
            out.block_until_ready()
        end = time.perf_counter()
        ends.append(end)
        if len(samples) < keep:
            samples.append((calls, j, out))
        else:
            r = int(rng.integers(0, calls + 1))
            if r < keep:
                samples[r] = (calls, j, out)
        del out
        calls += 1
        if end - start >= seconds:
            return Window(start=start, end=end, calls=calls,
                          signals=calls * batch, samples=samples, ends=ends)


def sample_size(out):
    """How many results the sample may hold: their largest shard, summed,
    stays under SAMPLE_BYTES on any device."""
    shard = max(s.data.nbytes for s in out.addressable_shards)
    return int(max(1, min(MAX_SAMPLES, SAMPLE_BYTES // shard)))


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------
def max_rel_err(samples, pool, reference, batch):
    """The worst, over the sampled results, of max |got - want| over
    max |want|; a non-finite result reads infinite."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(got, want):
        d = jnp.abs(got - want)
        d = jnp.where(jnp.isfinite(got), d, jnp.inf)
        return jnp.max(d), jnp.max(jnp.abs(want))

    chunk = reference.chunk(batch)
    worst = []
    for _, j, out in samples:
        diff = scale = 0.0
        for i0 in range(0, batch, chunk):
            want = reference(pool[j][i0:i0 + chunk])
            got = jax.device_put(out[i0:i0 + chunk], reference.device)
            d, s = (float(v) for v in gaps(got, want))
            diff, scale = max(diff, d), max(scale, s)
        worst.append(diff / scale if scale > 0 else float("inf"))
    return worst


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cell: Cell
    window: Window
    setup_s: float
    device_kind: str
    events: object = None  # devtrace.Events of a --trace 1 run


def read_metrics(defs, run):
    """Each metric from its reader: ``metrics/<name>.py``, where a name
    ``<quantity>.<cells>`` (one quantity split by the cells it is read
    in) is read by its quantity's reader."""
    out = {}
    for m in defs:
        quantity = m["name"].split(".")[0]
        reader = importlib.import_module(f"bench.metrics.{quantity}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def prepare(cell, seed, wrap=None):
    """The input pool, the entry and the warm-up: returns (pool, entry,
    how many results the sample keeps)."""
    t = time.perf_counter()
    pool = make_pool(cell, seed)
    cell.facts["pool_s"] = time.perf_counter() - t
    entry = cell.plan.compiled("apply")
    if wrap is not None:
        entry = wrap(entry, cell)
    tc = time.perf_counter()
    for _ in range(WARM_CALLS):
        out = entry(pool[0]).block_until_ready()
    cell.facts["first_calls_s"] = time.perf_counter() - tc
    return pool, entry, sample_size(out)


def program_facts(cell, entry, x):
    """Kernels and memory of the compiled program.  Read after the
    window of a traced run: lowering a plan whose graph is compiled in
    takes seconds that no call needs."""
    t = time.perf_counter()
    compiled = entry.lower(x).compile()
    cell.facts["facts_s"] = time.perf_counter() - t
    cell.facts["kernels"] = kernels_in(compiled)
    mem = compiled.memory_analysis()
    if mem is not None:
        cell.facts["program_bytes"] = {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes}


def check(spec, graph, samples, pool, batch, device, precision="highest"):
    """max_rel_err of each sampled result against the reference."""
    from bench.reference import Reference

    op = spec.config["operator"]
    reference = Reference(graph, op["K"], op["J"], device, precision)
    tr = time.perf_counter()
    errs = max_rel_err(samples, pool, reference, batch)
    log(f"reference check of {len(errs)} results: "
        f"{time.perf_counter() - tr:.2f} s")
    return errs


def run(workload, seed, seconds, trace, *, t0=None, root=ROOT,
        require_chip=True, spec=None, plan_overrides=None, wrap=None):
    """One run of `workload`; returns the result line's object.

    `spec` replaces the cell's files, `plan_overrides` adds plan
    options and `wrap(entry, cell)` replaces the timed entry: the
    benchmark's own tests use them to drive a run at a small size with a
    broken path underneath.
    """
    t0 = time.perf_counter() if t0 is None else t0
    import jax

    from bench import devtrace

    spec = spec or load_spec(workload, root)
    devices = chips_for(int(spec.workload["chips"]), require_chip)
    cache = use_compile_cache() if require_chip else None
    log(f"[{workload}] seed {seed}, {len(devices)} x "
        f"{devices[0].device_kind} found at {time.perf_counter() - t0:.3f} "
        f"s, compile cache {cache}")

    cell = build_cell(spec, devices, plan_overrides)
    pool, entry, keep = prepare(cell, seed, wrap)
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))

    setup_s = time.perf_counter() - t0
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        with devtrace.record(trace_dir):
            window = drive(entry, pool, cell.batch, seconds, keep, rng)
    else:
        window = drive(entry, pool, cell.batch, seconds, keep, rng)
    memory_peak = peak_bytes(devices)
    if trace and wrap is None:
        program_facts(cell, entry, pool[0])
    for k, v in cell.facts.items():
        print(f"# {k}: {v}", flush=True)
    print(f"# window: {window.calls} calls, {window.signals} signals in "
          f"{window.seconds:.6f} s; {len(window.samples)} sampled; peak "
          f"HBM {memory_peak} B", flush=True)
    print(f"# call_s: {window.call_seconds()}", flush=True)
    if len(devices) > 1 and wrap is None:
        print_messages(cell)

    events = None
    if trace:
        events = devtrace.Events.load(trace_dir, [d.id for d in devices])
        _rmtree(trace_dir)
    kind = devices[0].device_kind
    metrics = read_metrics(
        spec.per_layer if trace else spec.end_to_end,
        Run(cell=cell, window=window, setup_s=setup_s, device_kind=kind,
            events=events))

    # free the program's state; keep the sampled results and their inputs
    used = {j for _, j, _ in window.samples}
    pool = {j: x for j, x in enumerate(pool) if j in used}
    graph, batch = cell.graph, cell.batch
    del entry, cell
    jax.clear_caches()
    gc.collect()
    errs = check(spec, graph, window.samples, pool, batch, devices[0])
    limit = float(spec.checks["max_rel_err"]["limit"])
    err = max(errs)
    result = {
        "correct": bool(err <= limit),
        "attempted": window.calls,
        "failed": sum(e > limit for e in errs),
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": memory_peak},
    }
    if trace:
        result["device"].update(busy_s=events.busy_s(),
                                window_s=events.window_s())
        result["breakdown"] = events.breakdown()
    result["checks"] = {"max_rel_err": {"value": err, "limit": limit}}
    for name, c in result["checks"].items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}")
    return result


def print_messages(cell):
    """Messages of one apply, counted from its trace, against the
    paper's 2K|E|."""
    import jax

    from repro.dist.commstats import measure

    K = cell.operator["K"]
    stats = measure(cell.plan.apply,
                    jax.ShapeDtypeStruct((cell.batch, cell.graph.n),
                                         np.float32),
                    n_shards=len(cell.devices), batch=cell.batch,
                    ppermutes_per_round=cell.plan.info[
                        "exchange_collectives_per_round"])
    msgs = stats.paper_messages(cell.graph.n_edges)
    print(f"# messages: {msgs}, 2K|E| = {2 * K * cell.graph.n_edges}, "
          f"exchange rounds {stats.exchange_rounds}, bytes per shard "
          f"{stats.bytes_per_shard}", flush=True)


def _rmtree(path):
    import shutil

    shutil.rmtree(path, ignore_errors=True)
