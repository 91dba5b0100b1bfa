"""Operation and byte counts against hand arithmetic at a reduced size."""
import pytest

from chipbench.counts import llama, mamba1

LLAMA = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 2, "d_ff": 16, "vocab_size": 10}
MAMBA = {"n_layers": 2, "d_model": 32, "d_inner": 64, "ssm_state": 4,
         "conv_width": 4, "vocab_size": 10}


def test_llama_decode_iteration():
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 576 matmul
    # params, + 16 norm scales; head 10*8 = 80
    flops, byts = llama.decode_iteration(LLAMA, [3, 5])
    assert flops == 2 * 2 * (2 * 576 + 80) + 2 * (4 * 4 * 2) * 8
    kv_tok = 2 * 2 * 2                       # k and v, 2 heads x 2
    assert byts == 2 * (2 * 592 + 80 + 8 + 2 * 8 + 2 * kv_tok * (8 + 2))


def test_llama_prefill_chunk():
    flops, byts = llama.prefill_chunk(LLAMA, 4, 6)
    keys = 7 + 8 + 9 + 10
    assert flops == 4 * 2 * 2 * 576 + 2 * 32 * keys
    assert byts == 2 * (2 * 592 + 4 * 8 + 2 * 8 * (6 + 4))


def test_mamba_decode_iteration():
    # r = 2; per layer matmuls 32*128 + 64*10 + 2*64 + 64*32 = 6912
    mm = 32 * 128 + 64 * 10 + 2 * 64 + 64 * 32
    assert mm == 6912
    layer_bytes = 2 * (mm + 4 * 64 + 2 * 64 + 32) + 4 * 64 * 5
    state = 4 * 64 * 4 + 2 * 3 * 64
    tok = 2 * mm + 2 * 4 * 64 + 7 * 64 * 4
    flops, byts = mamba1.decode_iteration(MAMBA, [100, 7, 1])
    assert flops == 3 * (2 * tok + 2 * 320)
    assert byts == 2 * layer_bytes + 2 * (320 + 32) + 3 * (
        2 * 32 + 2 * 2 * state)


def test_mamba_prefill_chunk():
    mm = 6912
    layer_bytes = 2 * (mm + 4 * 64 + 2 * 64 + 32) + 4 * 64 * 5
    state = 4 * 64 * 4 + 2 * 3 * 64
    tok = 2 * mm + 2 * 4 * 64 + 7 * 64 * 4
    flops, byts = mamba1.prefill_chunk(MAMBA, 16, 48)
    assert flops == 16 * 2 * tok
    assert byts == 2 * layer_bytes + 2 * 16 * 32 + 2 * 2 * state


@pytest.mark.parametrize("mod,m", [(llama, LLAMA), (mamba1, MAMBA)])
def test_decode_counts_only_live_rows(mod, m):
    one = mod.decode_iteration(m, [4])
    two = mod.decode_iteration(m, [4, 4])
    none = mod.decode_iteration(m, [])
    assert two[0] == 2 * one[0] - none[0]
    assert two[1] == 2 * one[1] - none[1]
