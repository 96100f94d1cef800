"""The benchmark's own FLOP and byte counts against a count by hand for
OLMo-1B (d 2048, 16 layers, 16 heads of 128, d_ff 8192, vocab 50304)."""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import flops  # noqa: E402

CFG = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())
CFG4 = json.loads((BENCH / "configs" / "olmo-1b-4l.json").read_text())

# per layer: q, k, v, o = 4 x 2048 x 2048; SwiGLU 3 x 2048 x 8192
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 8192        # 67,108,864
EMBED = 50304 * 2048                             # 103,022,592


def test_params():
    assert flops.layer_matmul_params(CFG) == LAYER == 67108864
    assert flops.param_count(CFG) == 16 * LAYER + EMBED == 1176764416
    assert flops.param_count(CFG4) == 4 * LAYER + EMBED == 371458048


def test_forward_and_train_flops():
    # one token at position 0 sees one key: 2 x (all matmul weights)
    # + 4 x layers x d (scores and values)
    assert flops.decode_flops(CFG, 0) == 2 * 1176764416 + 4 * 16 * 2048
    # a 4-token prompt sees 1+2+3+4 = 10 keys
    assert flops.prefill_flops(CFG, 4) == (4 * 2 * 1176764416
                                           + 10 * 4 * 16 * 2048)
    seq = 2048
    keys = seq * (seq + 1) // 2
    assert flops.train_flops_per_sequence(CFG4, seq) == 3 * (
        seq * 2 * 371458048 + keys * 4 * 4 * 2048)


def test_decode_bytes():
    # K and V of one position: 2 x 16 layers x 16 heads x 128 x 2 bytes
    assert flops.kv_bytes_per_token(CFG) == 131072
    assert flops.decode_min_bytes(CFG, 1000) == (2 * 1176764416
                                                 + 131072 * 1000)
