"""A model joins the benchmark as new files: the model modules behind
the harness, the reference over any parameter pytree and in blocks of
users, and the program's spans handed to the per-layer readers."""
import hashlib
import json
import sys
import time
import types

import numpy as np
import pytest

from conftest import BENCH, TINY

SEED = 2_147_483_659          # above 2**31, as the driver's seeds are
# inputs and ``check`` numbers at ``conftest.TINY`` and ``SEED``, written
# from the harness as it stood before the model modules were split out
# of it (CPU backend, float32 at Precision.HIGHEST)
PINNED = json.loads((BENCH / "tests" / "data" / "pinned_tiny.json")
                    .read_text())


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def checked(spec, workload):
    """(inputs, minibatch indices, check numbers) of one cell at
    ``SEED``, as a benchmark run's set-up and comparison make them."""
    import check
    import gen
    import harness
    import reference

    _, cfg, traffic, _, _ = harness.load_cell(workload, spec)
    harness.configure_jax(cfg)
    inputs = gen.make_inputs(cfg, SEED)
    sels = gen.minibatch_indices(inputs.shards, cfg["L"], cfg["batch_size"],
                                 inputs.mb_seed, harness.CHECKED_ROUNDS)
    _, got = harness.checked_rounds(harness.build_engine(cfg, traffic,
                                                         inputs))
    numbers = check.against_reference(
        reference.Reference(cfg, gen.model(cfg)), inputs, sels, got,
        cfg["b"])
    return inputs, sels, numbers


@pytest.mark.parametrize("workload", ["c10-t3-wire", "fmnist-t2-wire"])
def test_pinned_inputs_and_numbers(spec, workload):
    """The CNN behind its model module draws the same inputs and reads
    the same ``check`` numbers, bit for bit, as before the split."""
    import jax

    assert PINNED["seed"] == SEED and PINNED["tiny"] == TINY
    want = PINNED["cells"][workload]
    inp, sels, numbers = checked(spec, workload)
    pos = {k: None if v is None else digest(v)
           for k, v in (("ap_pos", inp.ap_pos), ("user_pos", inp.user_pos))}
    assert {"x": digest(inp.x), "y": digest(inp.y), "n_train": inp.n_train,
            "shards": digest(*inp.shards),
            "params0": digest(*jax.tree_util.tree_leaves(inp.params0)),
            **pos, "mb_seed": inp.mb_seed,
            "sels": digest(*sels)} == want["inputs"]
    assert numbers == want["numbers"]


# --------------------------------------------------- a model as files
MLP = '''"""A two-layer perceptron on the paper's images, with a nested
parameter pytree: a model that joins the benchmark as this file alone."""
import numpy as np

from models import paper_cnn

FOLD_BLOCK = 3          # K=4 users: a block of 3, then 1
make_data = paper_cnn.make_data


def init_params(cfg, seed):
    import jax
    import jax.numpy as jnp

    n_in = cfg["input_hw"] ** 2 * cfg["channels"]
    h, nc = cfg["dense_units"], cfg["n_classes"]

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        return {"hidden": {"w": jax.random.normal(k1, (n_in, h))
                           / np.float32(np.sqrt(n_in)),
                           "b": jnp.zeros(h, jnp.float32)},
                "out": [jax.random.normal(k2, (h, nc))
                        / np.float32(np.sqrt(h)),
                        jnp.zeros(nc, jnp.float32)]}

    return make(jax.random.PRNGKey(seed))


def forward(p, x, mm):
    import jax.numpy as jnp

    h = mm(x.reshape(x.shape[0], -1), p["hidden"]["w"]) + p["hidden"]["b"]
    return mm(jnp.maximum(h, 0), p["out"][0]) + p["out"][1]


def loss(p, x, y, mm):
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(forward(p, x, mm))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def engine_inputs(cfg, inputs):
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import ImageDataset
    from repro.fl.models import ModelSpec

    n = inputs.n_train
    sets = [ImageDataset(x=inputs.x[a:b], y=inputs.y[a:b],
                         n_classes=cfg["n_classes"])
            for a, b in ((0, n), (n, len(inputs.y)))]
    params0 = jax.tree_util.tree_map(jnp.asarray, inputs.params0)

    def accuracy(p, x, y):
        return float(jnp.mean(jnp.argmax(forward(p, x, jnp.dot), -1) == y))

    spec = ModelSpec(name=cfg["model"], init=lambda key: params0,
                     loss=lambda p, x, y: loss(p, x, y, jnp.dot),
                     accuracy=accuracy)
    return sets[0], sets[1], spec
'''


@pytest.fixture
def mlp_spec(spec, tmp_path, monkeypatch):
    """The benchmark's spec with cell ``c10-t3-wire`` on a configuration
    whose model is ``mlp-probe``: a module found in ``tmp_path``, put on
    the model package's path."""
    import models

    (tmp_path / "mlp_probe.py").write_text(MLP)
    monkeypatch.setattr(models, "__path__",
                        list(models.__path__) + [str(tmp_path)])
    monkeypatch.delitem(sys.modules, "models.mlp_probe", raising=False)
    conf = next(c for c in spec["configs"]
                if c["name"] == "cnn-cifar10-table3")
    cfg = json.loads(open(conf["file"]).read())
    cfg.update(name="mlp-probe-cifar10", model="mlp-probe")
    path = tmp_path / "mlp-probe-cifar10.json"
    path.write_text(json.dumps(cfg))
    spec["configs"].append(dict(conf, name=cfg["name"], file=str(path)))
    for w in spec["workloads"]:
        if w["name"] == "c10-t3-wire":
            w["config"] = cfg["name"]
    yield spec
    sys.modules.pop("models.mlp_probe", None)


@pytest.mark.parametrize("fault, correct", [(None, True),
                                            ("frozen", False),
                                            ("half_users", False)])
def test_new_model_is_new_files(mlp_spec, tmp_path, fault, correct):
    """The model module alone, through a whole run: true, and false
    under two faults planted in the timed path."""
    import harness
    import test_correct

    out = harness.run(harness.Options(
        workload="c10-t3-wire", seed=SEED, seconds=0.5, trace=False,
        require_chip=False, spec=mlp_spec,
        patch=fault and getattr(test_correct, fault)),
        time.perf_counter())
    assert sys.modules["models.mlp_probe"].__file__ == \
        str(tmp_path / "mlp_probe.py")
    assert out["correct"] is correct, out["checks"]


# ------------------------------------------------------- the reference
def test_compare_nested_pytree():
    import check

    rng = np.random.default_rng(0)

    def tree(f):
        return {"blocks": [{"w": f((8, 4)), "b": f((4,))},
                           {"w": f((4, 4)), "b": f((4,))}],
                "head": (f((4, 2)),)}

    base = tree(lambda s: rng.standard_normal(s).astype(np.float32))
    step = tree(lambda s: rng.standard_normal(s).astype(np.float32))
    names = sorted(check.leaves(base))
    assert names == ["['blocks'][0]['b']", "['blocks'][0]['w']",
                     "['blocks'][1]['b']", "['blocks'][1]['w']",
                     "['head'][0]"]
    want = check.leaves(step)
    new = {k: check.leaves(base)[k] + want[k] for k in want}
    got = {k: v.copy() for k, v in new.items()}
    k = "['blocks'][1]['w']"
    got[k] = check.leaves(base)[k] + 1.25 * want[k]

    def back(flat):
        return {"blocks": [{"w": flat["['blocks'][0]['w']"],
                            "b": flat["['blocks'][0]['b']"]},
                           {"w": flat["['blocks'][1]['w']"],
                            "b": flat["['blocks'][1]['b']"]}],
                "head": (flat["['head'][0]"],)}

    bits = [np.array([10.0, 12.0])]
    same = check.compare({"params": [back(new)], "bits": bits},
                         {"params": [back(new)], "bits": bits,
                          "latency": []}, base, 4)
    assert same["update_diff"] == same["update_gap"] == 0.0
    off = check.compare({"params": [back(got)], "bits": bits},
                        {"params": [back(new)], "bits": bits,
                         "latency": []}, base, 4)
    norms = {n: np.linalg.norm(v.astype(np.float64)) for n, v in want.items()}
    den = max(norms[k], float(np.median(list(norms.values()))))
    assert off["update_diff"] == pytest.approx(0.25 * norms[k] / den,
                                               rel=1e-5)
    assert off["update_gap"] == pytest.approx(off["update_diff"], rel=1e-5)
    # a flat dict's leaves keep their names
    assert list(check.leaves({"conv_w": 1.0})) == ["['conv_w']"]


def test_fold_in_blocks_of_one_agrees_with_one_sum(spec):
    """Users folded one at a time, in user order, agree with the single
    sum over all K within float32 round-off, round by round from the
    same models."""
    import gen
    import harness
    import reference

    import jax

    _, cfg, _, _, _ = harness.load_cell("c10-t3-wire", spec)
    harness.configure_jax(cfg)
    inputs = gen.make_inputs(cfg, SEED)
    sels = gen.minibatch_indices(inputs.shards, cfg["L"], cfg["batch_size"],
                                 inputs.mb_seed, harness.CHECKED_ROUNDS)
    model = gen.model(cfg)
    whole = reference.Reference(cfg, model).run(inputs, sels)
    starts = [inputs.params0] + whole["params"][:-1]
    one_at_a_time = types.SimpleNamespace(loss=model.loss, FOLD_BLOCK=1)
    ones = reference.Reference(cfg, one_at_a_time).run(inputs, sels,
                                                        starts=starts)
    K = cfg["K"]
    eps = np.finfo(np.float32).eps
    for t, base in enumerate(starts):
        assert np.array_equal(ones["bits"][t], whole["bits"][t])
        np.testing.assert_allclose(ones["latency"][t], whole["latency"][t],
                                   rtol=1e-9)
        for a, b, p0 in zip(*(jax.tree_util.tree_leaves(x) for x in
                              (ones["params"][t], whole["params"][t],
                               base))):
            upd = np.abs(b.astype(np.float64) - p0)
            # K float32 additions of the update, and the add to the model
            tol = (K + 1) * eps * (np.max(upd) + np.abs(p0))
            assert np.all(np.abs(a.astype(np.float64) - b) <= tol)


# --------------------------------------------------- spans to readers
def test_eval_ms_reads_the_program_spans():
    from metrics import eval_ms

    spans = [{"kind": "phase", "name": "eval", "dur_s": 0.100},
             {"kind": "phase", "name": "engine.draw", "dur_s": 0.003},
             {"kind": "phase", "name": "eval", "dur_s": 0.120}]
    assert eval_ms.read({"spans": spans}) == pytest.approx(110.0)
    assert eval_ms.read({"spans": spans[1:2]}) is None
    assert eval_ms.read({"spans": []}) is None


def test_traced_run_hands_program_spans_to_readers(spec, monkeypatch):
    """The traced branch opens an obs session around its rounds and
    the readers find the program's ``eval`` spans (20 rounds from round
    4: rounds 10 and 20 evaluate).  The CPU's trace has no TPU planes,
    so the trace's reduction is stood in for."""
    import harness
    import xplane

    def reduce(path, n_devices, host_spans):
        lo, hi = host_spans[0][1], host_spans[-1][2]
        return {"ops": {}, "async_ops": {}, "modules": {}, "spans": [],
                "window_ns": (lo, hi), "busy_s": 1e-3,
                "window_s": (hi - lo) * 1e-9,
                "breakdown": {"device_ops": [], "idle_gaps": []}}

    monkeypatch.setattr(xplane, "reduce", reduce)
    spec["per_layer"] = [m for m in spec["per_layer"]
                         if m["name"] == "eval_ms"]
    out = harness.run(harness.Options(
        workload="fmnist-t2-wire", seed=SEED, seconds=0.5, trace=True,
        require_chip=False, spec=spec), time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["attempted"] == 20
    assert out["metrics"]["eval_ms"]["unit"] == "ms"
    assert out["metrics"]["eval_ms"]["value"] > 0
