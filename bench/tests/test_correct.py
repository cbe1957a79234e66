"""``correct`` at a size a test run holds, on the CPU: a sound run passes,
and the control and every fault a cell can have fail.

The runs skip the harness's look for a chip and drive the rest of it:
the configuration's files with a smaller graph and batch, the program on
its jnp path (the Pallas kernels are the chip's), the cell's own limit.
"""
import json
import os
import subprocess
import sys

import pytest

from bench import harness
from bench.calibrate import control_entry as control

ROOT = harness.ROOT
SMALL = {"sensor1m.apply_b64": 4000, "sensor500.apply_b64": 500}
SEED = 2 ** 31 + 12345


def small_spec(workload, batch=8):
    spec = harness.load_spec(workload)
    spec.config["graph"]["n"] = SMALL.get(workload, 4000)
    spec.traffic["batch"] = batch
    return spec


def run_small(workload, wrap=None, plan_overrides=None):
    overrides = {"use_pallas": None}
    overrides.update(plan_overrides or {})
    return harness.run(workload, SEED, 0.5, False, require_chip=False,
                       spec=small_spec(workload), plan_overrides=overrides,
                       wrap=wrap)


def unchanged(entry, cell):
    """Returns its input as every multiplier's output: nothing applied."""
    import jax.numpy as jnp

    eta = cell.facts["eta"]
    return lambda x: jnp.broadcast_to(x[:, None, :], (x.shape[0], eta,
                                                      x.shape[1]))


def half_batch(entry, cell):
    """Applies the filter bank to half the batch and repeats it."""
    import jax.numpy as jnp

    def half(x):
        y = entry(x[: x.shape[0] // 2])
        return jnp.concatenate([y, y])

    return half


def altered(entry, cell):
    """One answer of each call changed where it is produced."""
    import jax.numpy as jnp

    def alter(x):
        y = entry(x)
        return y.at[0, 0, 0].add(0.01 * jnp.max(jnp.abs(y)))

    return alter


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(workload):
    r = run_small(workload)
    assert r["correct"], r["checks"]
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0


@pytest.mark.parametrize("workload", sorted(SMALL))
@pytest.mark.parametrize("fault", [control, unchanged, half_batch, altered],
                         ids=lambda f: f.__name__)
def test_control_and_faults_are_not_correct(workload, fault):
    r = run_small(workload, wrap=fault)
    assert not r["correct"], r["checks"]
    c = r["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]


# sensor1m's configuration sharded over four devices: the path a
# four-chip cell drives, with the exchange between them
FOUR_DEVICES = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
from bench import harness
from bench.tests import test_correct
spec = harness.load_spec("sensor1m.apply_b64")
spec.workload = dict(spec.workload, chips=4)
spec.config["graph"]["n"] = 4000
spec.traffic["batch"] = 8
r = harness.run("sensor1m.apply_b64", {seed}, 0.5, False,
                require_chip=False, spec=spec,
                plan_overrides={overrides},
                wrap={fault!r} and getattr(test_correct, {fault!r}))
print(json.dumps(r))
"""


@pytest.mark.parametrize("overrides, fault, correct", [
    ({}, None, True),
    # the exchange between chips left out: every received tile dropped
    ({"fault_spec": {"drop_prob": 1.0}, "degradation": "zero_fill"}, None,
     False),
    ({}, "control", False),
    ({}, "unchanged", False),
    ({}, "half_batch", False),
    ({}, "altered", False),
])
def test_four_shards_exchange(overrides, fault, correct):
    code = FOUR_DEVICES.format(root=ROOT, src=os.path.join(ROOT, "src"),
                               seed=SEED, overrides=overrides, fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"] is correct, r["checks"]


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "sensor500.apply_b64", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
