"""``correct`` has to come out false when the timed path is broken, and
when the control (the reference in int8) stands in for the program.
Each test drives a whole run through ``run.main`` at smoke size on the
CPU, with the look for a chip skipped; only the fault differs.  A sound
run of the same cell has to come out true.
"""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

import chiplib  # noqa: E402

jax = pytest.importorskip("jax")

CFG_FILE = json.loads((BENCH / "tests" / "data" / "olmo-smoke.json")
                      .read_text())
LENS = {"prompt_lens": [16, 32], "prompt_weights": [0.5, 0.5],
        "output_lens": [4, 8], "output_weights": [0.5, 0.5]}
MIXES = {
    "train": {"driver": "train", "global_batch": 2, "seq_len": 64,
              "schedule": "sharded-async", "retention": 2,
              "commit_every": 4, "crash_after_commit": 2, "peak_lr": 3e-4,
              "rate_metric": "durable_train_tokens_per_s",
              "total_steps": 10000},
    "serve": {"driver": "serve", "n_slots": 4, "t_max": 96, **LENS,
              "arrival": {"kind": "backlog", "depth": 8},
              "durable": {"commit_every": 2, "schedule": "sync",
                          "retention": 2, "retire_done": True}},
}
#: limits for the smoke-size cells (the chip cells' own are in limits/)
LIMITS = {"train": {"loss_gap": 1e-3, "grad_norm_gap": 1e-3,
                    "change_norm_gap": 5e-3},
          "serve": {"served_logit_gap": 0.03}}


@pytest.fixture
def harness(monkeypatch, capsys):
    """``run(kind)`` runs one smoke cell through ``run.main`` and returns
    its result line."""
    spec = {"configs": [{"name": "smoke", "file": "-"}],
            "workloads": [{"name": f"smoke.{k}", "config": "smoke",
                           "traffic": k, "chips": 1} for k in MIXES],
            "end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": []}
    monkeypatch.setattr(chiplib, "benchmark_spec", lambda: spec)
    monkeypatch.setattr(chiplib, "config_file", lambda s, n: CFG_FILE)
    monkeypatch.setattr(chiplib, "traffic_file", lambda n: MIXES[n])
    monkeypatch.setattr(chiplib, "limits_file",
                        lambda c: LIMITS[c.split(".")[1]])
    monkeypatch.setattr(chiplib, "check_device",
                        lambda d, c, p: {"bf16_flops_per_s": 1.0,
                                         "hbm_bytes_per_s": 1.0})
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")
    import run as run_mod

    def run(kind, seed=2 ** 32 + 7):
        rc = run_mod.main(["--workload", f"smoke.{kind}", "--seed",
                           str(seed), "--seconds", "3", "--trace", "0"])
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    return run


def _patch_step(monkeypatch, wrap):
    import repro.train.step as step_mod
    make = step_mod.make_train_step
    monkeypatch.setattr(step_mod, "make_train_step",
                        lambda *a, **kw: wrap(make(*a, **kw)))


def test_sound_runs_are_correct(harness):
    for kind in MIXES:
        out = harness(kind)
        assert out["correct"], out["checks"]


def test_train_state_unchanged(harness, monkeypatch):
    def wrap(step):
        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return unchanged
    _patch_step(monkeypatch, wrap)
    out = harness("train")
    assert not out["correct"]
    assert out["checks"]["change_norm_gap"]["value"] > 0.9


def test_train_half_batch(harness, monkeypatch):
    def wrap(step):
        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    _patch_step(monkeypatch, wrap)
    assert not harness("train")["correct"]


def test_serve_token_altered(harness, monkeypatch):
    import repro.serve.engine as engine_mod
    make = engine_mod.make_slot_decode_step
    vocab = CFG_FILE["vocab_size"]

    def altered(*a, **kw):
        decode = make(*a, **kw)

        def step(*args):
            toks, logits, caches, pos = decode(*args)
            return (toks + 1) % vocab, logits, caches, pos
        return step
    monkeypatch.setattr(engine_mod, "make_slot_decode_step", altered)
    out = harness("serve")
    assert not out["correct"]


@pytest.mark.parametrize("seed", [5, 6, 2 ** 32 + 7])
def test_control_fails_the_limits(seed):
    """The int8 reference in the program's place reads past the limits:
    training on at least one number, serving on the logit gap of its own
    picks."""
    drv_t = chiplib.driver_module("train")
    a = chiplib.RunArgs(cell="smoke.train", config=CFG_FILE,
                        traffic=MIXES["train"], seed=seed, seconds=1,
                        trace=False)
    ref = drv_t.reference_numbers(a)
    gaps = drv_t.gaps(drv_t.reference_numbers(a, quant="int8"), ref)
    assert any(v > LIMITS["train"][k] for k, v in gaps.items()), gaps

    from repro.models.registry import build
    olmo = chiplib.reference(CFG_FILE)
    cfg = chiplib.program_config(CFG_FILE)
    params = olmo.to_f32(chiplib.arch(CFG_FILE).make_params(
        build(cfg, dec_pos_len=96).abstract_params(), seed, CFG_FILE))
    rng = np.random.default_rng(seed)
    worst = 0.0
    for _ in range(6):
        prompt = tuple(int(t) for t in rng.integers(
            0, CFG_FILE["vocab_size"], size=32))
        served = [int(t) for t in rng.integers(0, CFG_FILE["vocab_size"],
                                               size=56)]
        _, ctrl_gap = olmo.served_gaps(CFG_FILE, params, prompt, served,
                                       quant="int8")
        worst = max(worst, float(np.max(ctrl_gap)))
    assert worst > LIMITS["serve"]["served_logit_gap"]
