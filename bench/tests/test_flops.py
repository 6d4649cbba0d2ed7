"""Operation and byte counts against hand counts at small shapes."""
import pytest

from bench import flops as F
from bench.models import dense_gqa

PEAKS = {"bf16_flops_per_s": 100.0, "int8_ops_per_s": 200.0,
         "hbm_bytes_per_s": 10.0}
DENSE = {"family": "dense", "n_layers": 2, "d_model": 4, "n_heads": 2,
         "n_kv_heads": 1, "head_dim": 2, "d_ff": 8, "vocab_size": 10}


def test_dense_token_flops():
    # per layer: wq 4x4, wk 4x2, wv 4x2, wo 4x4, mlp 3 x 4x8 = 144 weights
    # 2 layers x 2 x 144 = 576; head 2 x 10 x 4 = 80; attention at
    # context 5: 4 x 2 heads x 2 x 5 = 80 per layer, 160
    f = dense_gqa.token_flops(DENSE, "float", 5.0)
    assert f == {"bf16": 576 + 80 + 160, "int8": 0.0}


def test_dense_token_flops_on_the_int8_path():
    f = dense_gqa.token_flops(DENSE, "int8", 5.0)
    assert f == {"bf16": 80 + 160, "int8": 576.0}


def test_least_seconds_takes_the_binding_bound():
    assert F.least_seconds({"bf16": 100.0, "int8": 200.0}, 5.0, PEAKS) \
        == pytest.approx(2.0)
    assert F.least_seconds({"bf16": 100.0}, 50.0, PEAKS) == \
        pytest.approx(5.0)


def test_decode_attention_call():
    # float: row = 2 x 1 kv head x 2 x 2 bytes + 4 = 12; 30 rows -> 360;
    # q and out: 2 x 3 slots x 2 heads x 2 x 2 bytes = 48.  Ops 4 x 2 x 2
    # x 20 live rows = 320.
    ops, nbytes = F.decode_attention_call(DENSE, "float", 3, 30, 20)
    assert (ops, nbytes) == ({"bf16": 320.0}, 408.0)
    # int8: row = 2 x 2 x 1 + 4 + 2 x 1 x 4 = 16
    ops, nbytes = F.decode_attention_call(DENSE, "int8", 3, 30, 20)
    assert nbytes == 30 * 16 + 48
