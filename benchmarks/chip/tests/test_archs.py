"""Everything model-specific is found by a configuration file's
``model_type``: ``archs/<model_type>.py`` and ``reference/<model_type>.py``.
OLMo's counts and weights are what they were before they moved there, and
a second architecture is taken through new files alone.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import chiplib  # noqa: E402
import flops  # noqa: E402

SMOKE = json.loads((BENCH / "tests" / "data" / "olmo-smoke.json")
                   .read_text())
SECOND = BENCH / "tests" / "data" / "second_arch"
CONFIGS = [SMOKE] + [json.loads(p.read_text())
                     for p in sorted((BENCH / "configs").glob("*.json"))]

#: sha256 over the leaves' bytes of the OLMo smoke weights, as the
#: N(0, initializer_range) draw made them before the architecture's
#: module took it over
SMOKE_DIGESTS = {
    0: "bd8b636d43c2417b806d836997e750280952312b38fb6fb39cfe90c3562906e1",
    2 ** 33 + 1:
        "9ca92939693d2c6bf6030e20e878a2a76e1f2833ebd5ec222b4acaee4517bdd6",
}


def _abstract(cfg_file):
    from repro.models.registry import build
    return build(chiplib.program_config(cfg_file)).abstract_params()


def _digest(tree) -> str:
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(SMOKE_DIGESTS))
def test_olmo_weights_unchanged(seed):
    pytest.importorskip("jax")
    abstract = _abstract(SMOKE)
    made = chiplib.arch(SMOKE).make_params(abstract, seed, SMOKE)
    plain = chiplib.make_params(abstract, seed, SMOKE["initializer_range"])
    assert _digest(made) == _digest(plain) == SMOKE_DIGESTS[seed]


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_param_count_is_the_programs(cfg):
    """The count from the published shapes and the program's own tree
    (shapes only, nothing is allocated) agree leaf for leaf in sum."""
    jax = pytest.importorskip("jax")
    n = sum(int(np.prod(leaf.shape)) for leaf in
            jax.tree_util.tree_leaves(_abstract(cfg)))
    assert flops.param_count(cfg) == n


@pytest.mark.parametrize("cfg", CONFIGS, ids=lambda c: c["name"])
def test_olmo_decode_bytes_ignore_slots(cfg):
    base = flops.decode_min_bytes(cfg, 5000)
    for slots in (1, 8, 32):
        assert flops.decode_min_bytes(cfg, 5000, decode_slots=slots) == base


def test_program_config_refuses_a_disagreement():
    bad = dict(SMOKE, num_key_value_heads=2)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        chiplib.program_config(bad)


@pytest.mark.parametrize("kind,lookup", [("archs", chiplib.arch),
                                         ("reference", chiplib.reference)])
def test_unknown_model_type_names_the_file(kind, lookup):
    cfg = dict(SMOKE, model_type="no_such_model")
    with pytest.raises(FileNotFoundError,
                       match=f"{kind}/no_such_model.py"):
        lookup(cfg)
    if kind == "archs":
        with pytest.raises(FileNotFoundError, match="archs/no_such_model"):
            flops.param_count(cfg)


#: run in the copy of the harness, on the CPU: what chiplib resolves for
#: the second architecture's cell
_PROBE = """
import json, sys
sys.path.insert(0, "benchmarks/chip")
import jax, numpy as np
import chiplib, flops
from repro.models.registry import build
spec = chiplib.benchmark_spec()
cell = chiplib.find_cell(spec, "internlm2-smoke.serve-chat")
cfg = chiplib.config_file(spec, cell["config"])
pc = chiplib.program_config(cfg)
params = chiplib.arch(cfg).make_params(build(pc).abstract_params(),
                                       2 ** 40 + 3, cfg)
flat = jax.tree_util.tree_flatten_with_path(params)[0]
ref = chiplib.reference(cfg)
print(json.dumps({
    "arch_file": chiplib.arch(cfg).__file__, "ref_file": ref.__file__,
    "program": [pc.n_kv_heads, pc.norm, pc.tied_embeddings],
    "n_program": sum(int(l.size) for _, l in flat),
    "param_count": flops.param_count(cfg),
    "prefill_flops": flops.prefill_flops(cfg, 16),
    "decode_bytes": [flops.decode_min_bytes(cfg, 100, decode_slots=s)
                     for s in (1, 4)],
    "norms_one": all(bool(np.all(np.asarray(l, np.float32) == 1))
                     for p, l in flat if "norm" in jax.tree_util.keystr(p)),
    "others_std": [float(np.std(np.asarray(l, np.float32))) for p, l in flat
                   if "norm" not in jax.tree_util.keystr(p)],
    "ref_names": sorted(n for n in ("to_f32", "logits", "loss_and_grad",
                                    "served_gaps", "train")
                        if callable(getattr(ref, n, None))),
    "limits": chiplib.limits_file(cell["name"]),
    "metrics": [m["name"] for m in chiplib.cell_metrics(spec, cell["name"],
                                                        True)],
}))
"""


def _tree_digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_second_architecture_by_new_files_only(tmp_path):
    pytest.importorskip("jax")
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, chip,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree_digests(chip)
    for src in sorted(SECOND.glob("*/*.*")):
        dst = chip / src.relative_to(SECOND)
        assert not dst.exists(), dst
        shutil.copy(src, dst)
    spec = chiplib.benchmark_spec()
    spec["configs"].append({
        "name": "internlm2-smoke", "source":
        "https://huggingface.co/internlm/internlm2-1_8b/blob/main/config.json",
        "file": "benchmarks/chip/configs/internlm2-smoke.json",
        "reduced": [], "why": "a second architecture at smoke size"})
    spec["workloads"].append({
        "name": "internlm2-smoke.serve-chat", "config": "internlm2-smoke",
        "traffic": "serve-chat", "chips": 1,
        "why": "the chat mix on a second architecture"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "olmo-1b.serve-chat" in m.get("workloads", []):
            m["workloads"].append("internlm2-smoke.serve-chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", _PROBE], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])

    assert Path(got["arch_file"]) == chip / "archs" / "internlm2.py"
    assert Path(got["ref_file"]) == chip / "reference" / "internlm2.py"
    assert got["program"] == [2, "rmsnorm", False]
    # 2 layers x (q, k, v, o 98,304 + SwiGLU 49,152 + 2 norms 128), a
    # final norm of 64, two 256 x 64 tables
    assert got["n_program"] == got["param_count"] == 328000
    layer = 64 * 128 * (2 * 4 + 2 * 2) + 3 * 64 * 256
    assert got["prefill_flops"] == (16 * 2 * (2 * layer + 256 * 64)
                                    + 136 * 4 * 2 * 4 * 128)
    assert got["decode_bytes"] == [2 * 328000 + 100 * 2 * 2 * 2 * 128 * 2] * 2
    assert got["norms_one"]
    assert all(0.015 < s < 0.025 for s in got["others_std"])
    assert got["ref_names"] == ["logits", "loss_and_grad", "served_gaps",
                                "to_f32", "train"]
    assert got["limits"] == {"served_logit_gap": 0.25}
    assert "decode_roofline.chat" in got["metrics"]

    after = _tree_digests(chip)
    assert {k: after[k] for k in before} == before


@pytest.mark.parametrize("slots", [1, 8])
def test_decode_roofline_reads_the_counts(slots):
    """decode_roofline.chat: the least bytes of the traced decode ticks
    over the HBM bandwidth, against the decode programs' device time; a
    tick without decode keys has no decode step."""
    import types
    import readers
    import trace_reduce
    cfg = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())
    ticks = [{"t0": 1.0, "t1": 1.1, "decode_keys": 1000,
              "decode_slots": slots},
             {"t0": 1.2, "t1": 1.3, "decode_keys": 0, "decode_slots": 0}]
    record = types.SimpleNamespace(host={"ticks": ticks,
                                         "window": (0.0, 2.0)})
    red = trace_reduce.Reduction(window_s=2.0, busy_s=1.0, n_devices=1,
                                 programs={"slot_decode": [0.01, 0.02]},
                                 ops=[], gaps=[])
    run = readers.Run("olmo-1b.serve-chat", cfg, {}, record, red,
                      {"hbm_bytes_per_s": 819e9})
    got = chiplib.metric_reader("decode_roofline.chat")(run)
    least = (2 * 1176764416 + 131072 * 1000) / 819e9
    assert got == pytest.approx(100 * least / 0.03, rel=1e-12)
