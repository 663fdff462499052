"""FLOP and byte counts the roofline shares and MFUs divide by."""
import json

import pytest

from benchpath import BENCH
from benchlib.flops import Decoder, flash_fwd, gemm_block


def qwen():
    return Decoder.from_config(json.loads(
        (BENCH / "configs" / "qwen2-0.5b.json").read_text()))


def test_gemm_block_counts():
    flops, nbytes = gemm_block(1024)
    assert flops == 2 * 1024 ** 3                     # 2.15 GFLOP
    assert nbytes == 4 * 1024 * 1024 * 4              # A, B, C read; C written
    assert gemm_block(4096)[0] == pytest.approx(137.4e9, rel=1e-3)


def test_flash_forward_counts():
    flops, nbytes = flash_fwd(1, 4, 2, 1, 8, causal=True)
    # 10 (query, key) pairs of 4 positions, 4 FLOPs per pair and lane
    assert flops == 4 * 2 * 8 * 10
    assert nbytes == 2 * 4 * 8 * (2 * 2 + 2 * 1)
    assert flash_fwd(1, 4, 2, 1, 8, causal=False)[0] == 4 * 2 * 8 * 16


def test_qwen2_decoder_shapes():
    m = qwen()
    assert (m.layers, m.d_model, m.heads, m.kv_heads, m.head_dim, m.d_ff,
            m.vocab) == (24, 896, 14, 2, 64, 4864, 151936)
    per_layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert m.layer_matmul_params == per_layer
    assert m.matmul_params == 24 * per_layer + 151936 * 896
    # about 3.1 GFLOP a trained token, 0.82 of it the tied output head
    assert 3 * m.token_flops(512) == pytest.approx(3.1e9, rel=0.05)


def test_decode_step_counts_each_slot_at_its_context():
    m = qwen()
    one = m.token_flops(100)
    assert one - 2 * m.matmul_params == 4 * 24 * 14 * 64 * 100
    assert m.decode_step_flops([100, 100, 7]) == pytest.approx(
        2 * one + m.token_flops(7))


def test_train_step_is_three_forward_passes_of_every_position():
    m = Decoder(2, 16, 2, 1, 8, 32, 50)
    want = 3 * 3 * sum(m.token_flops(t) for t in range(1, 9))
    assert m.train_step_flops(3, 8) == pytest.approx(want)
