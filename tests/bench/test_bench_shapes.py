"""Operation and byte counts of a decoder position, against hand counts."""
from bench.shapes import decoder_step, grle

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


def paper():
    from bench import manifest
    return manifest.load_json(manifest.BENCH_DIR / "configs"
                              / "grle_paper.json")


def test_gcn_agg_by_hand():
    # 2 graphs, 3 self vertices of 5 features, 4 neighbours of 6, into 7
    flops, data = grle.gcn_agg(2, 3, 4, 5, 6, 7)
    assert flops == 2 * 2 * (3 * 4 * 6 + 3 * 5 * 7 + 3 * 6 * 7)
    assert data == 4 * (2 * (3 * 4 + 3 * 5 + 4 * 6 + 3 * 7)
                        + 5 * 7 + 6 * 7 + 7)


def test_edge_score_by_hand():
    # 2 graphs, 3 devices, 4 options, h2 5, edge hidden 6
    flops, data = grle.edge_score(2, 3, 4, 5, 6)
    assert flops == 2 * 2 * (3 * 5 * 6 + 4 * 5 * 6 + 2 * 3 * 4 * 6)
    assert data == 4 * (2 * (3 * 5 + 4 * 5 + 2 * 3 * 4) + 2 * 5 * 6
                        + 3 * 6 + 1)


def test_actor_flops_at_the_papers_sizes():
    # M 14, O 10, features 7 and 4, hidden 128 and 64, edge hidden 64:
    # layer 1 devices 20,272 MACs, options 15,060; layer 2 devices
    # 247,296, options 181,760; edge MLP 116,224
    assert grle.actor_flops(paper()) == 2 * (20272 + 15060 + 247296
                                             + 181760 + 116224)


def test_episode_at_the_papers_sizes():
    cfg = paper()
    assert grle.train_steps(cfg, 64, 200) == 20
    per_graph = 1161224
    assert grle.episode_flops(cfg, 64, 200) == (
        200 * 64 * per_graph + 20 * 3 * 64 * per_graph)
    calls = grle.episode_kernel_calls(cfg, 64, 200)
    assert len(calls) == (200 + 20) * 5
    assert sum(1 for k, *_ in calls if k == "edge_score") == 220


def test_no_train_step_before_the_ring_holds_a_minibatch():
    cfg = paper()
    # one fleet fills the 64-graph minibatch at slot 64: steps at 70..200
    assert grle.train_steps(cfg, 1, 200) == 14
