"""Each FLOP and byte function against a count worked by hand at a small
shape."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from lib import flops  # noqa: E402

# 2 layers, d 8, 2 query heads of 4, 1 KV head, gated MLP of 16, vocab 10
# stored as 16 rows
GQA = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
       "vocab_size": 10, "padded_vocab_size": 16, "gated_mlp": True}
# the same without KV grouping, a plain MLP and an MLM transform
MLM = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
       "intermediate_size": 16, "vocab_size": 10, "padded_vocab_size": 16,
       "gated_mlp": False, "mlm_transform": True}


def test_matmul_params():
    # q 8*8 + k, v 2*8*4 + o 8*8 + mlp 3*8*16 = 64 + 64 + 64 + 384
    assert flops.layer_matmul_params(GQA) == 576
    assert flops.matmul_params(GQA) == 2 * 576 + 8 * 10
    # q, k, v, o 4*64 + mlp 2*8*16; plus the MLM dense 8*8 and the head
    assert flops.layer_matmul_params(MLM) == 512
    assert flops.matmul_params(MLM) == 2 * 512 + 64 + 80


def test_train_flops_per_token():
    # 6 N + 12 L s d with s = 5, d = h dh = 8
    assert flops.train_flops_per_token(MLM, 5) == 6 * 1168 + 12 * 2 * 5 * 8


def test_decode_step_and_kernels():
    # weights: (2*576 + 8*16) * 2 bytes; KV: 2 layers * (k, v) * 1 * 4 * 2
    assert flops.weight_bytes(GQA) == 2560
    assert flops.kv_bytes_per_token(GQA) == 32
    assert flops.attention_decode_flops(GQA, 3) == 4 * 2 * 2 * 4 * 3
    assert flops.decode_step(GQA, [3, 5]) == (2 * 2 * 1232 + 4 * 2 * 8 * 8,
                                             2560 + 32 * 8)
    # keys and values of 8 tokens, plus q and out: 2 layers * 2 * 8 * 2 B
    # for each of the 2 sequences
    assert flops.paged_attention(GQA, [3, 5]) == (4 * 2 * 8 * 8,
                                                 32 * 8 + 2 * 2 * 8 * 2 * 2)
    # 2 rows d V over 4 rows; head 8*16*2 B, hidden 4*8*2 B
    assert flops.lm_head(GQA, 4) == (2 * 4 * 8 * 16, 256 + 64)


def test_roofline_seconds_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(200.0, 10.0, peak) == (2.0, "flops")
    assert flops.roofline_seconds(100.0, 30.0, peak) == pytest.approx(
        (3.0, "bytes"))
