"""Operations and bytes from shapes, against counts made by hand."""
import json

from conftest import BENCH
from harness import flops, model


def _shapes(name):
    return model.Shapes.of(json.loads(
        (BENCH / "configs" / f"{name}.json").read_text()))


def test_qwen3_4b():
    s = _shapes("qwen3-4b")
    # q 2560x4096, k and v 2560x1024, o 4096x2560, MLP 3 x 2560x9728
    per_layer = 2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728
    assert flops.layer_matmul_params(s) == per_layer == 100_925_440
    S = 2048
    want = (2 * S * 36 * per_layer + 4 * 36 * 32 * 128 * S * (S + 1) // 2
            + 2 * 2560 * 151936)
    assert flops.prefill_flops(s, S) == want
    # weights: tied embedding, 36 layers, two norms each, final norm
    assert s.weight_bytes() == 2 * (151936 * 2560 + 36 * per_layer
                                    + 36 * 2 * 2560 + 2560)
    assert s.kv_bytes_per_token() == 147_456
    assert flops.decode_bytes(s, 100) == s.weight_bytes() + 100 * 147_456


def test_qwen_7b_chat_l16():
    s = _shapes("qwen-7b-chat-l16")
    per_layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert flops.layer_matmul_params(s) == per_layer
    S = 3584
    want = (2 * S * 16 * per_layer + 4 * 16 * 32 * 128 * S * (S + 1) // 2
            + 2 * 4096 * 151936)
    assert flops.prefill_flops(s, S) == want
    params = (2 * 151936 * 4096 + 16 * (per_layer + 3 * 4096 + 2 * 4096)
              + 4096)
    assert s.weight_bytes() == 2 * params == 8_965_988_352
    assert s.kv_bytes_per_token() == 262_144
