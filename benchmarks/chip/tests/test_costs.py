"""The cost functions against a hand count at a tiny shape: the dense
family's counts of a call, and the shared byte and roofline arithmetic."""
import costs
from driver import Call
from spec import load_family

F = load_family("dense_gqa")
A = F.Arch(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
           d_ff=16, vocab=32, tied=False, qkv_bias=True,
           rope_theta=1e4, eps=1e-5, window=0)


def test_counts_by_hand():
    # q: 8x8, k and v: 8x4 each, o: 8x8, mlp: 3 x 8x16
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert F.layer_matmul_params(A) == per_layer == 576
    assert F.matmul_params(A) == 2 * 576 + 32 * 8
    # K and V, one kv head of 4, two layers, bf16
    assert F.kv_bytes_per_token(A) == 2 * 2 * 1 * 4 * 2
    # one token attending 3 positions: 2 flops per weight, plus QK and PV
    assert F.token_flops(A, 3) == 2 * 1408 + 4 * 2 * 2 * 4 * 3
    # causal prefill of 3 tokens attends 1 + 2 + 3 positions
    assert F.prefill_flops(A, 3) == sum(F.token_flops(A, c) - 0
                                        for c in (1, 2, 3))


def test_decode_call_by_hand():
    flops, nbytes = F.decode_call(A, Call("decode", 0, 0.0, 1.0, 2,
                                          depths=[5, 0]))
    assert flops == F.token_flops(A, 6) + F.token_flops(A, 1)
    weights = 2 * (576 * 2 + 2 * 8 * 4 + 16 * 2) + 8 * 4 + 32 * 8 * 2 \
        + 2 * 8 * 2
    assert F.weight_bytes_read(A, 2) == weights
    assert nbytes == weights + 5 * 32 + 2 * 32


def test_call_flops_by_hand():
    admit = Call("admit", 0, 0.0, 1.0, 2, prompt_lens=[3, 1])
    assert F.call_flops(A, admit) == F.prefill_flops(A, 3) \
        + F.prefill_flops(A, 1)
    decode = Call("decode", 0, 0.0, 1.0, 2, depths=[5, 0])
    assert F.call_flops(A, decode) == F.decode_call(A, decode)[0]


def test_top2gap_bytes_and_ideal():
    assert costs.top2gap_bytes(8, 32000) == 8 * 32000 * 4 + 2 * 8 * 128 * 4
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.ideal_seconds(1000.0, 50.0, peak) == 10.0
    assert costs.ideal_seconds(100.0, 50.0, peak) == 5.0
