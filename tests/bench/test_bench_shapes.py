"""Operation and byte counts of a decoder position, against hand counts."""
from bench.shapes import decoder_step

CFG = dict(hidden_size=8, intermediate_size=12, num_attention_heads=2,
           num_key_value_heads=1, vocab_size=20)


def test_flops_by_hand():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x12 each, down 12x8 = 320 MACs
    assert decoder_step.layer_weights(CFG) == 64 + 32 + 32 + 64 + 288
    # per layer: 2 * 480 + scores and values 2 * 2 * (2 heads * 4) * kv_len
    kv = 5
    per_layer = 2 * 480 + 2 * 2 * 8 * kv
    head = 2 * 8 * 20
    assert decoder_step.flops(CFG, 3, kv) == 3 * per_layer + head


def test_step_bytes_by_hand():
    # 2 layers of 480 weights + head 160, bf16; K and V of 1 head x 4 dims
    # per layer for rows attending to 3 and 7 positions
    weights = (2 * 480 + 160) * 2
    kv = 2 * 2 * 1 * 4 * (3 + 7) * 2
    assert decoder_step.step_bytes(CFG, 2, [3, 7]) == weights + kv


def test_qwen_full_depth_step_reads_its_weights():
    from bench import manifest
    m = manifest.load_json(manifest.BENCH_DIR / "configs"
                           / "qwen05b_edge.json")["model"]
    b = decoder_step.step_bytes(m, 24, [1])
    # 24 layers of 12.85M weights plus the 155.6M-weight head, in bf16
    assert 0.92e9 < b < 0.94e9
