"""A configuration's architecture comes from its own file.

The harness loads ``chipbench/archs/<class>.py`` for the class a
configuration's ``model.architectures`` names, and every piece that depends
on the model's layers goes through it. Pinned here: the dense Qwen2
module gives exactly the numbers the harness gave before the pieces were
reached through it (yardstick counts of both configurations, the weights at
toy widths, the reference's logits); and a new architecture is new files
only, which a copy of the benchmark's tree in a temporary directory shows."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import run as R
from chipbench.tests._tiny import tiny_cell

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 77

# the harness's numbers before the architecture modules (CPU)
PINNED = {
    "qwen2-0.5b-edge": {
        "dims": {"layers": 24, "d": 896, "heads": 14, "kv_heads": 2,
                 "head_dim": 64, "ffn": 4864, "vocab": 151936,
                 "tied": True},
        "layer_matmul_params": 14909440, "kv_bytes_per_token": 12288,
        "decode_attention": (8601600.0, 1486848.0),
        "append_attention": (9031680.0, 1044480.0),
        "model_step": 5554110475.0},
    "qwen2-72b-tp8pp2": {
        "dims": {"layers": 40, "d": 8192, "heads": 8, "kv_heads": 1,
                 "head_dim": 128, "ffn": 3696, "vocab": 19008,
                 "tied": False},
        "layer_matmul_params": 109707264, "kv_bytes_per_token": 20480,
        "decode_attention": (16384000.0, 2539520.0),
        "append_attention": (17203200.0, 1945600.0),
        "model_step": 62058921995.0},
}
PARAMS_SHA256 = (
    "6067d21e459b8d3c19b05548555c13ccf82ce2ebfbe027de30ae0a652f97aae0")
# float32 logits on one CPU core (more cores sum in another order)
LOGITS_SHA256 = {
    "float32":
        "d8d7a44f5b13ef22b3997e24e7c1be84df5a1435100e365d1109a602e9c028f7",
    "int8":
        "0ea9644922df61f9fac6cc7ceae9c722221958f5393d85da8298703f66069c5e"}


def _config(name):
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(PINNED))
def test_yardstick_is_pinned(name):
    config = _config(name)
    a = R.arch(config)
    assert Path(a.__file__) == ROOT / "chipbench/archs/Qwen2ForCausalLM.py"
    dims = a.dims(config["model"])
    want = PINNED[name]
    assert dims == want["dims"]
    # the counts test_chipbench_yardstick.py checks by hand
    assert a.layer_matmul_params(dims) == want["layer_matmul_params"]
    assert a.kv_bytes_per_token(dims) == want["kv_bytes_per_token"]
    assert a.decode_attention(dims, 3, 100) == want["decode_attention"]
    assert a.append_attention(dims, 10, 105, 15) == want["append_attention"]
    assert a.model_step(dims, 7, 2, 11) == want["model_step"]


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_program_params_are_pinned():
    config = tiny_cell()["config"]
    cfg, dims = R.model_config(config)
    assert cfg.family == "dense" and cfg.n_layers == 2
    params = R.arch(config).program_params(SEED, dims, config["init"])
    assert _digest(params) == PARAMS_SHA256


LOGITS = r"""
import hashlib, json, os
os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
import numpy as np
from chipbench import reference, run as R
from chipbench.tests._tiny import tiny_cell
config = tiny_cell()["config"]
rng = np.random.default_rng(0)
rows = [rng.integers(259, 512, n).tolist() for n in (40, 23)]
a = R.arch(config)


def digest(quant, at):
    st, top = a.final_states(%d, config["model"], config["init"], rows,
                             quant=quant)
    lg = [np.asarray(reference.logits_at(st[i], top, p))
          for i, p in enumerate(at)]
    return hashlib.sha256(b"".join(x.tobytes() for x in lg)).hexdigest()


print(json.dumps({"float32": digest(None, [range(35, 40), range(18, 23)]),
                  "int8": digest("int8", [[0, 10, 39]])}))
""" % SEED


def _python(code: str, cwd: Path) -> dict:
    """Run ``code`` in a fresh CPU process in ``cwd``; its last line of
    output, as JSON."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_reference_logits_are_pinned():
    # one core, so that XLA sums in the order the pinned digests were taken
    assert _python(LOGITS, ROOT) == LOGITS_SHA256


def test_unknown_architecture_names_the_file():
    with pytest.raises(R.BenchError, match=r"archs/NoSuchForCausalLM\.py"):
        R.arch({"model": {"architectures": ["NoSuchForCausalLM"]}})


NEW_CELL = r"""
import json
from chipbench import run as R
cell = R.load_cell("copy-tiny.shared")
cfg, dims = R.model_config(cell["config"])
res = R.run(cell, %d, 1.0, False, out_dir=R.ROOT / "out")
print(json.dumps({"arch": R.arch(cell["config"]).__file__,
                  "family": cfg.family, "correct": res["correct"],
                  "compared": res["compared"]}))
""" % (SEED + 1)


def _files(root: Path) -> dict:
    return {str(p.relative_to(root)): p.read_bytes()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_architecture_is_new_files_only(tmp_path):
    """A copy of the benchmark's tree gains a model whose class the harness
    has never seen: its architecture module (here the Qwen2 one under
    another name), its configuration, a mix, and entries in
    ``BENCHMARK.json``. Nothing that was there changes, and the whole run,
    reference comparison included, goes through the new module."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _files(root)

    shutil.copy(root / "chipbench/archs/Qwen2ForCausalLM.py",
                root / "chipbench/archs/CopyForCausalLM.py")
    cell = tiny_cell()
    config = dict(cell["config"], name="copy-tiny")
    config["model"] = dict(config["model"],
                           architectures=["CopyForCausalLM"])
    (root / "chipbench/configs/copy-tiny.json").write_text(
        json.dumps(config))
    (root / "chipbench/traffic/tiny-shared.json").write_text(
        json.dumps(cell["traffic"]))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "copy-tiny", "source": config["source"],
        "file": "chipbench/configs/copy-tiny.json", "reduced": [],
        "why": "a class the harness has not seen"})
    bench["workloads"].append({
        "name": "copy-tiny.shared", "config": "copy-tiny",
        "traffic": "tiny-shared", "chips": 1, "why": "toy widths"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _files(root)
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {"BENCHMARK.json"}
    old = json.loads(before["BENCHMARK.json"])
    assert all(bench[k][:len(v)] == v if isinstance(v, list)
               else bench[k] == v for k, v in old.items())
    assert set(after) - set(before) == {
        "chipbench/archs/CopyForCausalLM.py",
        "chipbench/configs/copy-tiny.json",
        "chipbench/traffic/tiny-shared.json"}

    got = _python(NEW_CELL, root)
    assert Path(got["arch"]) == root / "chipbench/archs/CopyForCausalLM.py"
    assert got["family"] == "dense"
    assert got["correct"] is True, got["compared"]
    assert got["compared"]["served_tokens_compared"]["value"] > 0
