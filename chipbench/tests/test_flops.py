"""FLOP and byte counts against hand counts at small sizes."""
import pytest

from chipbench import flops


def test_decoder_flops_per_token_hand_count():
    m = {"hidden_size": 8, "intermediate_size": 16, "vocab_size": 32,
         "num_attention_heads": 2, "num_key_value_heads": 1, "num_hidden_layers": 3}
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8 = 192; MLP 3 x 8x16 = 384
    matmul = 3 * (192 + 384) + 8 * 32          # + the LM head; the embedding counts 0
    attention = 12 * 3 * 10 * 2 * 4           # layers, seq 10, heads, head_dim
    assert flops.decoder_train_flops_per_token(m, seq=10) == 6 * matmul + attention


def test_yi_2l_step_flops():
    m = {"hidden_size": 4096, "intermediate_size": 11008, "vocab_size": 64000,
         "num_attention_heads": 32, "num_key_value_heads": 4, "num_hidden_layers": 2}
    step = 2048 * flops.decoder_train_flops_per_token(m, seq=512)
    assert step == pytest.approx(7.58e12, rel=0.01)   # not 6 x 870 M x 2048 = 10.7e12


def test_fnn_round_flops_hand_count():
    m = {"kind": "fnn", "dims": [4, 3, 2]}
    fwd = 2 * (4 * 3 + 3 * 2)
    assert flops.sample_forward_flops(m) == fwd
    # 2 chains x 5 steps x batch 7: forward and backward; then the loss of 2 x 7
    assert flops.round_flops(m, chains=2, walk=5, batch=7) == 3 * fwd * 70 + fwd * 14


def test_lstm_forward_hand_count():
    m = {"kind": "lstm", "vocab": 10, "embed": 3, "hidden": 2, "layers": 2, "seq_len": 4}
    cell1 = 2 * (3 * 8 + 2 * 8)
    cell2 = 2 * (2 * 8 + 2 * 8)
    assert flops.sample_forward_flops(m) == 4 * (cell1 + cell2) + 2 * 2 * 10


def test_qdq_round_bytes_hand_count():
    # M=2 chains, K=3 steps, a 256-lane model (2 rows)
    hop = 2 * (3 * 256 + 2 * 2) * 4           # payload, base, output; two f32 per row
    agg = 6 * (2 * 256 + 2 * 2) * 4
    assert flops.qdq_round_bytes(256, chains=2, walk=3) == 3 * hop + agg
