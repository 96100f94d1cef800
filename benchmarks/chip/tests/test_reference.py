"""The plain float32 reference against the program's prefill, slot decode
and training loss, at smoke size on the CPU.  The program runs in float32
here (weights and compute), so the two must agree to float32 rounding;
the benchmark itself compares against the bfloat16 program on the chip.
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
import jax.numpy as jnp  # noqa: E402

CFG_FILE = json.loads((BENCH / "tests" / "data" / "olmo-smoke.json")
                      .read_text())
T_MAX = 48


@pytest.fixture(scope="module")
def setup():
    from repro.models.registry import build
    olmo = chiplib.reference(CFG_FILE)
    cfg = chiplib.program_config(CFG_FILE).with_(
        param_dtype="float32", compute_dtype="float32")
    bundle = build(cfg, dec_pos_len=T_MAX)
    params = chiplib.make_params(bundle.abstract_params(), 2 ** 33 + 1,
                                 0.3)
    return olmo, bundle, params


def _close(a, b, tol=2e-4):
    a, b = np.asarray(a), np.asarray(b)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b)))


def test_prefill_then_slot_decode(setup):
    from repro.train.step import make_slot_decode_step
    olmo, bundle, params = setup
    rng = np.random.default_rng(0)
    seq = rng.integers(0, CFG_FILE["vocab_size"], size=24).astype(np.int32)
    plen = 16
    with jax.default_matmul_precision("highest"):
        ref = olmo.logits(CFG_FILE, params, seq[None])[0]
        caches = bundle.init_caches(jax.random.PRNGKey(0), 1, T_MAX)
        last, st = bundle.prefill(params, {"tokens": jnp.asarray(
            seq[None, :plen])}, caches)
        _close(last[0], ref[plen - 1])
        decode = jax.jit(make_slot_decode_step(bundle))
        caches, pos = st.caches, jnp.asarray([plen], jnp.int32)
        for t in range(plen, len(seq)):
            _, lg, caches, pos = decode(
                params, jnp.asarray([[seq[t]]], jnp.int32), caches, pos,
                jnp.asarray([True]))
            _close(lg[0], ref[t])


def test_train_loss_and_grad(setup):
    olmo, bundle, params = setup
    rng = np.random.default_rng(1)
    tok = rng.integers(0, CFG_FILE["vocab_size"], size=(2, 33)).astype(
        np.int32)
    batch = {"tokens": jnp.asarray(tok[:, :-1]),
             "targets": jnp.asarray(tok[:, 1:])}
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda p: bundle.loss(p, batch, with_remat=True),
            has_aux=True)(params)
    ref_loss, ref_grads = olmo.loss_and_grad(CFG_FILE, params,
                                             tok[:, :-1], tok[:, 1:])
    _close(loss, ref_loss, 1e-5)
    for g, r in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(ref_grads)):
        _close(g, r, 1e-3)


def test_served_gaps_zero_on_reference_argmax(setup):
    olmo, _, params = setup
    prompt = tuple(range(1, 9))
    served = []
    seq = list(prompt)
    for _ in range(5):
        lg = olmo.logits(CFG_FILE, params, np.asarray(seq, np.int32)[None])
        served.append(int(np.argmax(np.asarray(lg)[0, -1])))
        seq.append(served[-1])
    gap, ctrl = olmo.served_gaps(CFG_FILE, params, prompt, served,
                                 quant="int8")
    assert np.max(gap) == 0.0 and ctrl.shape == gap.shape
    altered = served[:2] + [(served[2] + 1) % CFG_FILE["vocab_size"]] + \
        served[3:]
    gap2, _ = olmo.served_gaps(CFG_FILE, params, prompt, altered)
    assert gap2[2] > 0
