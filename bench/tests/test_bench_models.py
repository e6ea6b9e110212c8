"""Backbones are plug-ins found by `model.arch` (`reference.backbone`): a
second architecture is a new file, and ResNet-18-CIFAR's module computes
what the reference computed before it moved there."""
import hashlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import generate
import harness
import reference
import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = {"arch": "toy-patch", "patch": 4, "in_channels": 3, "width": 32,
       "projector": [32, 128]}

# pin_run.py's output at the commit before the ResNet-18-CIFAR code moved
# out of reference.py into models/resnet18-cifar.py (reference.init_model
# and reference.forward then), on this CPU with two cores. The images
# digest is also that of the default float32 pool.
PINNED = {
    "r18-single.paper-b256": {
        "images": "52e68dc3ee1ce0b1209bf7b5a85e9726c20b1423bc12d5d7e994b886614dc6f2",
        "weights": "ef98655823ccc8992f0627e23ba22e420840bde7b371426ebdea230765c25bd5",
        "losses": ["2.017121195793152", "2.6555246114730835"],
        "models": ["6d0206e34d8b7e092c76d45646eb7920943fbc2eb066dd8bd5cb530fcacaa948",
                   "7a37d8d9572f2cf4f1a7c80808829217031cffa7b3568aceb031b7dc205b9fdd"],
        "grad_norms": "02cc1104732d8475126962aac9098823cb7093f5a680dd157d75e198071e6e91"},
    "r18-handover.q8-b256": {
        "images": "52e68dc3ee1ce0b1209bf7b5a85e9726c20b1423bc12d5d7e994b886614dc6f2",
        "weights": "ef98655823ccc8992f0627e23ba22e420840bde7b371426ebdea230765c25bd5",
        "losses": ["1.982975423336029", "2.420015573501587"],
        "models": ["062e9333934fc54379ab935e01b094264aebc4b3cf471ae122311302bb49b35a",
                   "ced3009120ec23a9f29d7c966863a109219b648c839f3ea66bef982318250c99"],
        "grad_norms": "4fdfd90f1339fb2b629f3deccf72cd676a17d5cd80edb1f75d3242884729e1fb"},
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_resnet_reference_keeps_its_bytes(cell):
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "pin_run.py"), cell],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-4000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == PINNED[cell]


def _bench_files():
    """Digest of every file of the benchmark but caches and outputs."""
    h = hashlib.sha256()
    for root, dirs, files in sorted(os.walk(harness.BENCH)):
        dirs[:] = sorted(d for d in dirs if d not in ("__pycache__", "out"))
        for f in sorted(files):
            with open(os.path.join(root, f), "rb") as fh:
                h.update(f.encode() + fh.read())
    return h.hexdigest()


def _toy_cell(monkeypatch, workload, **cfg):
    monkeypatch.setattr(reference, "MODELS", os.path.join(HERE, "models"))
    c = harness.cell(harness.benchmark(), workload)
    return ({**c["cfg"], **tiny.CFG, "model": TOY, **cfg},
            {**c["traffic"], **tiny.TRAFFIC})


@pytest.mark.parametrize("workload", ["r18-single.paper-b256",
                                      "r18-handover.q8-b256"])
def test_toy_backbone_runs_generate_reference_and_check(workload,
                                                        monkeypatch):
    before = _bench_files()
    cfg, traffic = _toy_cell(monkeypatch, workload)
    s = generate.seeds(2**33 + 5)
    data = generate.images(cfg, s["data"])
    tree0 = generate.weights(cfg, s["weights"])
    assert tree0["state"] == {}
    assert set(tree0["params"]) == {"embed", "embed_b", "dense", "dense_b",
                                    "proj1", "proj1_b", "proj2"}
    ref = reference.run(cfg, traffic, data, tree0, s["fl"], 2)
    low = reference.run(cfg, traffic, data, tree0, s["fl"], 2,
                        dtype=jnp.bfloat16)
    model0 = reference.initial_model(cfg, tree0)
    same = check.numbers(ref, ref, model0)
    assert all(same[k] == 0.0 for k in check.NAMES), same
    got = check.numbers(low, ref, model0)
    assert got["change_gap"] > 0 and got["loss1_gap"] > 0
    assert got["schedule_gap"] == 0.0
    leaves = check._paths(model0)
    assert leaves and all("['params']" in k for k in leaves)
    assert "['params']" in got["_change_worst"]
    assert _bench_files() == before


def test_uint8_pool_is_scaled_before_the_blur(monkeypatch):
    cfg, traffic = _toy_cell(monkeypatch, "r18-single.paper-b256",
                             image_dtype="uint8")
    s = generate.seeds(12345)
    data = generate.images(cfg, s["data"])
    assert all(d.dtype == np.uint8 for d in data)
    pool = np.concatenate(data)
    assert pool.min() == 0 and pool.max() == 255
    tree0 = generate.weights(cfg, s["weights"])

    def run(pool):
        return reference.run(cfg, traffic, pool, tree0, s["fl"], 1)

    as_u8 = run(data)
    # the same pool in float32, scaled on the host: equal to float32
    # rounding (XLA fuses the cast and the division into the blur); the
    # pool unscaled reads far apart
    as_f32 = run([d.astype(np.float32) / np.float32(255) for d in data])
    unscaled = run([d.astype(np.float32) for d in data])
    np.testing.assert_allclose(as_u8["losses"], as_f32["losses"], rtol=1e-5)

    def close(other):
        return all(np.allclose(a, b, rtol=1e-4, atol=1e-6) for a, b in zip(
            jax.tree.leaves(as_u8["models"]),
            jax.tree.leaves(other["models"])))

    assert close(as_f32) and not close(unscaled)


def test_unknown_image_dtype_is_refused():
    cfg = {**tiny.CFG, "image_dtype": "float16"}
    with pytest.raises(ValueError, match="valid: \\['float32', 'uint8'\\]"):
        generate.images(cfg, 0)


def test_unknown_arch_names_the_valid_ones():
    with pytest.raises(SystemExit, match="'resnet18-cifar'"):
        reference.backbone("no-such-arch")
    cfg = {**tiny.CFG, "model": {**TOY, "arch": "no-such-arch"}}
    with pytest.raises(SystemExit, match="unknown model arch"):
        generate.weights(cfg, 0)
