"""``correct`` against the plain reference: a sound run reads true, the
control (the reference at precision ``high``) reads false, and so does
every fault that the cells can have, planted in the timed path
underneath a whole run (the chip check skipped, everything else as a
benchmark run does it)."""
import json
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, ROOT

SEED = 2_147_483_659          # above 2**31, as the driver's seeds are


def run_cell(spec, workload, patch=None):
    import harness

    return harness.run(harness.Options(
        workload=workload, seed=SEED, seconds=0.5, trace=False,
        require_chip=False, patch=patch, spec=spec), time.perf_counter())


# ------------------------------------------------------------ faults
def frozen(eng):
    """The round step hands back the model it was given."""
    import jax
    import jax.numpy as jnp

    step = eng._fused_step

    def keep(params, *args):
        old = jax.tree_util.tree_map(jnp.copy, params)
        out = step(params, *args)
        return (old,) + tuple(out[1:])

    eng._fused_step = keep


def restarts(eng):
    """Every round starts from the initial model: the model is not
    carried from one round to the next."""
    import jax
    import jax.numpy as jnp

    step = eng._fused_step
    first = []

    def again(params, *args):
        if not first:
            first.append(jax.tree_util.tree_map(jnp.copy, params))
        return step(jax.tree_util.tree_map(jnp.copy, first[0]), *args)

    eng._fused_step = again


def half_users(eng):
    """Half of the users left out, the weights renormalized over the
    rest."""
    w = np.array(eng.rho, np.float64)
    w[len(w) - len(w) // 2:] = 0.0
    eng._round_weights = lambda active: w / w.sum()


def full_power(eng):
    """The power solve's answer altered: every user at full power."""
    power = eng.power
    power.solve = lambda chan, bits: power._finish(
        chan, bits, np.ones(len(bits)))


def altered_update(eng):
    """One leaf of the model update altered where it is produced."""
    step = eng._fused_step

    def alter(params, qstate, *args):
        import jax.numpy as jnp

        before = jnp.copy(params["dense2_w"])
        out = step(params, qstate, *args)
        new = dict(out[0])
        new["dense2_w"] = before + 1.5 * (out[0]["dense2_w"] - before)
        return (new,) + tuple(out[1:])

    eng._fused_step = alter


@pytest.mark.parametrize("workload", ["c10-t3-wire", "fmnist-t2-wire"])
def test_sound_run_is_correct(spec, workload):
    out = run_cell(spec, workload)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1


@pytest.mark.parametrize("workload, fault", [
    ("c10-t3-wire", frozen), ("c10-t3-wire", half_users),
    ("c10-t3-wire", full_power), ("c10-t3-wire", altered_update),
    ("c10-t3-wire", restarts),
    ("fmnist-t2-wire", frozen), ("fmnist-t2-wire", half_users),
    ("fmnist-t2-wire", altered_update), ("fmnist-t2-wire", restarts),
])
def test_fault_is_not_correct(spec, workload, fault):
    out = run_cell(spec, workload, patch=fault)
    assert not out["correct"], (fault.__name__, out["checks"])


@pytest.mark.parametrize("workload", ["c10-t3-wire", "fmnist-t2-wire"])
def test_control_is_not_correct(workload):
    """The reference at precision ``high``, put in the program's place,
    fails the cell's limits on three seeds, at the cell's own size (the
    program is not run, so this fits a test run)."""
    import check
    import gen
    import harness
    import reference

    _, cfg, _, limits, _ = harness.load_cell(workload)
    ref = reference.Reference(cfg, gen.model(cfg), "highest")
    ctrl = reference.Reference(cfg, gen.model(cfg), "high")
    for seed in (SEED, 7, 12345):
        inputs = gen.make_inputs(cfg, seed)
        sels = gen.minibatch_indices(inputs.shards, cfg["L"],
                                     cfg["batch_size"], inputs.mb_seed,
                                     harness.CHECKED_ROUNDS)
        numbers = check.against_reference(ref, inputs, sels,
                                          ctrl.run(inputs, sels), cfg["b"])
        ok, checks = check.verdict(numbers, limits)
        assert not ok, (seed, checks)


FOUR_DEVICES = r'''
import json, sys, time
sys.path[:0] = [{bench!r}, {tests!r}]
from pathlib import Path
import numpy as np
from conftest import tiny_spec
import harness, test_correct
spec = tiny_spec(Path({tmp!r}))
# cell 1's configuration and traffic with its users sharded over a
# (4, 1) (data, model) mesh, held to cell 1's limits
cell = next(w for w in spec["workloads"] if w["name"] == "c10-t3-wire")
cell.update(chips=4)
load_cell = harness.load_cell
def on_mesh(workload, spec=None):
    cell, cfg, traffic, limits, spec = load_cell(workload, spec)
    return cell, cfg, dict(traffic, mesh=[4, 1]), limits, spec
harness.load_cell = on_mesh
def no_exchange(eng):
    """Each device folds only its own users' planes: the all-gather of
    the packed planes left out (headers still gathered for the bits)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.kernels.ops import mixed_res_encode, mixed_res_wire_reduce
    q, d, wp = eng.quantizer, eng.d, eng.wire_path_spec
    def agg(flat, weights):
        def local(flat_l, w):
            n = flat_l.shape[0]
            wire = mixed_res_encode(flat_l, q.lambda_, q.b, path=wp)
            i = jax.lax.axis_index("data")
            w_l = jax.lax.dynamic_slice(w, (i * n,), (n,))
            head = jax.lax.all_gather(wire.head, "data", tiled=True)
            return mixed_res_wire_reduce(wire, w_l, q.b, d, path=wp), head
        a, head = jax.shard_map(local, mesh=eng.engine_cfg.mesh,
                                in_specs=(P("data"), P()),
                                out_specs=(P(), P()),
                                check_vma=False)(flat, weights)
        bits, aux = eng._head_stats(head)
        return a, bits, aux
    eng._sharded_wire_aggregate = agg
res = {{}}
for name, patch in (("sound", None), ("no_exchange", no_exchange),
                    ("frozen", test_correct.frozen),
                    ("half_users", test_correct.half_users),
                    ("full_power", test_correct.full_power)):
    out = harness.run(harness.Options(
        workload="c10-t3-wire", seed={seed}, seconds=0.5,
        trace=False, require_chip=False, patch=patch, spec=spec),
        time.perf_counter())
    res[name] = out["correct"]
print(json.dumps(res))
'''


def test_four_device_cell(tmp_path):
    """Cell 1 with its users over four virtual CPU devices (a traffic
    with ``"mesh": [4, 1]``, for the four-chip cell still to come):
    sound run true, and false with the exchange between devices left out
    and with the faults of the one-device cells."""
    code = FOUR_DEVICES.format(bench=str(BENCH), tests=str(BENCH / "tests"),
                               tmp=str(tmp_path), seed=SEED)
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "HOME": str(tmp_path), "TMPDIR": str(tmp_path)}
    import os

    env["PYTHONPATH"] = str(ROOT / "src")
    for k in ("PATH", "LD_LIBRARY_PATH", "PYTHONHOME"):
        if k in os.environ:
            env[k] = os.environ[k]
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res == {"sound": True, "no_exchange": False, "frozen": False,
                   "half_users": False, "full_power": False}, res
