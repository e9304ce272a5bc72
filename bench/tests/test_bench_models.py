"""Model modules. ``opto_vit`` gives bitwise the weights and reference
logits, and exactly the op counts, that the harness gave before its ViT
code moved into ``bench/models/opto_vit.py`` (``pins.json``, recorded
with that harness). A new model is files only: in a copy of ``bench/``,
a configuration naming a module that only the copy holds is served and
checked end to end on the CPU, and one whose block differs gets its
weights, reference and op counts from its own module. A configuration
that names no module, or a missing one, is refused by its file's
name."""

from __future__ import annotations

import hashlib
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import loads, reference, run  # noqa: E402
from bench.models import opto_vit  # noqa: E402
from bench.tests.tiny import overrides, tiny_patches  # noqa: E402

HERE = Path(__file__).resolve().parent
PINS = json.loads((HERE / "pins.json").read_text())
CONFIGS = sorted(PINS["weights"])
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
FLOAT = {"quant_bits": 0,
         "backends": {"matmul": "bf16", "attn": "xla", "ffn": "xla"}}


def _config(name: str) -> dict:
    return json.loads((ROOT / "bench/configs" / f"{name}.json").read_text())


def _tree_digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.shape).encode() + str(a.dtype).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


# -- opto_vit is what it was -------------------------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_bitwise_the_pinned_ones(name):
    cfg = dict(_config(name), **PINS["tiny"])
    params = reference.make_init(opto_vit, cfg, PINS["seed"])
    assert _tree_digest(params) == PINS["weights"][name]


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_at_own_widths_are_bitwise_the_pinned_ones(name):
    """One layer at the configuration's own widths, where Base and Large
    differ (at Tiny widths their trees are the same)."""
    cfg = dict(_config(name), n_layers=1)
    params = reference.make_init(opto_vit, cfg, PINS["seed"])
    assert _tree_digest(params) == PINS["weights_one_layer"][name]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_logits_are_bitwise_the_pinned_ones(name):
    cfg = dict(_config(name), **PINS["tiny"])
    params = reference.make_init(opto_vit, cfg, PINS["seed"])
    rng = np.random.default_rng(np.random.SeedSequence([PINS["seed"], 1]))
    px = PINS["tiny"]["img_size"]
    x = rng.normal(size=(6, px, px, 3)).astype(np.float32)
    s = x.copy()
    s[3:] = rng.normal(size=(3, px, px, 3)).astype(np.float32)
    pin = PINS["logits"][name]
    logits = reference.ReferenceModel(opto_vit, params, cfg, pin["keep"],
                                      block=4).logits(x, s)
    assert hashlib.sha256(np.ascontiguousarray(
        logits, np.float32).tobytes()).hexdigest() == pin["digest"]


@pytest.mark.parametrize("key", sorted(PINS["ops"]))
def test_op_counts_are_exactly_the_pinned_ones(key):
    """A flush of ``n_real`` frames at ``kept`` patches, MGNet on as many
    frames and the patch embed of four times as many."""
    name, kept, n_real = key.split("/")
    kept, n_real = int(kept), int(n_real)
    w = opto_vit.frame_work(_config(name), 4 * n_real, n_real,
                            [(kept, n_real)])
    got = {k: [t.int8_ops, t.float_ops, t.bytes]
           for k, t in w.by_kernel.items()}
    assert got == PINS["ops"][key]


# -- a new model is files only -----------------------------------------------

def _copy_with_cell(tmp_path: Path, toy: str, config: str, changes: dict,
                    cell: str) -> Path:
    """A copy of ``bench/`` and ``BENCHMARK.json`` under ``tmp_path`` with
    the module ``toy`` in its ``bench/models/``, a configuration naming it
    (Base's with ``changes``), a four-camera traffic mix and a cell of
    ``base224-keep33``'s settings; no code file of the copy is edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = tmp_path / "bench"
    shutil.copy(HERE / "toy_models" / f"{toy}.py", bench / "models")
    cfg = dict(_config("opto-vit-base-224"), name=config, model=toy,
               **changes)
    (bench / "configs" / f"{config}.json").write_text(json.dumps(cfg))
    t = json.loads((bench / "traffic/closed16.json").read_text())
    t["cameras"] = 4
    (bench / "traffic/closed4.json").write_text(json.dumps(t))
    shutil.copy(bench / "workloads/base224-keep33.json",
                bench / "workloads" / f"{cell}.json")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": config, "source": "test",
                            "file": f"bench/configs/{config}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": config,
                              "traffic": "closed4", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "base224-keep33" in m.get("workloads", []):
            m["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_a_model_at_patch_14_is_served_and_checked(tmp_path):
    """The copy's own module runs the whole cell at 56 px, 16 patches of
    14 px: the program serves it, and the reference it names checks it.
    On the program's float32 path (as ``test_bench_reference.py`` runs
    it) every served class is the reference's best, so the cell's limits
    hold with room and any wiring fault would show as a gap."""
    cell = "toy14-keep33"
    root = _copy_with_cell(tmp_path, "toy_p14", "toy-p14-224",
                           {"patch": 14}, cell)
    c = run.load_cell(cell, root)
    assert Path(c["model"].__file__) == root / "bench/models/toy_p14.py"
    assert tiny_patches(cell, root) == 16
    ov = overrides(cell, root=root, **FLOAT)
    assert ov["config"]["img_size"] == 56
    res = run.run_cell(cell, 2**31 + 1414, 0.5, False, root=root,
                       overrides=ov, require_tpu=False)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["max_gap"]["value"] <= 1e-4
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["frames_per_s"]["value"] > 0


def test_a_model_with_another_block_brings_its_own_code(tmp_path):
    """Weights, the reference's forward pass and the op counts all come
    from the copy's module, whose block is not ``opto_vit``'s."""
    cell = "toyglu-keep33"
    root = _copy_with_cell(tmp_path, "toy_swiglu", "toy-swiglu-224", {},
                           cell)
    c = run.load_cell(cell, root, overrides(cell, root=root))
    model, cfg, keep = c["model"], c["cfg"], c["cell"]["keep_patches"]
    assert Path(model.__file__) == root / "bench/models/toy_swiglu.py"
    params = reference.make_init(model, cfg, 2**31 + 99)
    assert set(params["blocks"]["ffn"]) == {"w12", "w3"}
    assert params["reg"].shape == (1, 1, cfg["d_model"])

    rng = np.random.default_rng(5)
    px = cfg["img_size"]
    x = rng.normal(size=(3, px, px, 3)).astype(np.float32)
    s = rng.normal(size=(3, px, px, 3)).astype(np.float32)
    got = reference.ReferenceModel(model, params, cfg, keep,
                                   block=3).logits(x, s)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, a, b: model.classify(
            p, a, b, keep, cfg))(params, x, s))
    np.testing.assert_array_equal(got, want)
    # the register token is read
    moved = dict(params, reg=params["reg"] + 1.0)
    assert not np.allclose(reference.ReferenceModel(
        model, moved, cfg, keep, block=3).logits(x, s), got)

    work = run._window_work(model, cfg, loads.Served(
        frames=8, scored=2, flushes=[(keep, 2)]))
    assert set(work.by_kernel) == set(model.KERNELS) | {"other"}
    d, dff, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    rows = 2 * (keep + 2)                    # [cls] and the register ride
    assert work.by_kernel["fused_swiglu"].int8_ops == \
        L * (2 * rows * d * 2 * dff + 2 * rows * dff * d)
    assert work.by_kernel["flash_attention_masked"].float_ops == \
        L * 2 * 2 * 2 * (keep + 2) ** 2 * d


@pytest.mark.parametrize("model", [None, "no_such_model", "../run"])
def test_a_config_must_name_its_model_module(tmp_path, model):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    path = tmp_path / "bench/configs/opto-vit-base-224.json"
    cfg = json.loads(path.read_text())
    if model is None:
        del cfg["model"]
    else:
        cfg["model"] = model
    path.write_text(json.dumps(cfg))
    with pytest.raises((ValueError, FileNotFoundError),
                       match="opto-vit-base-224.json"):
        run.load_cell("base224-keep33", tmp_path)
