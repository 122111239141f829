"""Operation and byte counts against hand counts."""

import os

from chipbench import harness
from chipbench.families import gpt, resnet

GPT2 = harness.load_json(os.path.join(harness.HERE, "configs",
                                      "gpt2-small.json"))
RESNET = harness.load_json(os.path.join(harness.HERE, "configs",
                                        "resnet50.json"))


def test_gpt2_small_forward_ops_equal_a_hand_count_at_b1_s1024():
    cfg = gpt.build(GPT2)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.n_layers,
            cfg.d_ff, cfg.vocab_size) == (768, 12, 64, 12, 3072, 50304)
    # per token, per layer: qkv 2*768*2304, out 2*768*768,
    # MLP 2*2*768*3072, causal attention 2*2*1024*768/2
    per_layer = 3_538_944 + 1_179_648 + 9_437_184 + 1_572_864
    assert per_layer == 15_728_640
    head = 2 * 768 * 50304
    assert head == 77_266_944
    by_hand = 12 * per_layer + head
    assert by_hand == 266_010_624
    assert gpt.forward_flops_per_token(cfg, 1024) == by_hand
    assert gpt.train_flops_per_token(cfg, 1024) == 3 * by_hand
    # one sequence of 1,024 tokens: 0.817 TFLOP forward and backward
    assert abs(1024 * 3 * by_hand / 1e12 - 0.8172) < 1e-3


def test_attention_kernel_counts_at_the_train_cells_shape():
    cfg = gpt.build(GPT2)
    bhssd = 16 * 12 * 1024 * 1024 * 64
    assert gpt.attention_kernel_flops(cfg, 16, 1024) == 12 * 6 * bhssd
    assert gpt.attention_kernel_bytes(cfg, 16, 1024) == \
        12 * 12 * (16 * 12 * 1024 * 64) * 2
    # the operations bound applies on a v5e: 4.71 ms against 4.42 ms
    peaks = harness.peaks_for("TPU v5e")
    t_ops = gpt.attention_kernel_flops(cfg, 16, 1024) / peaks["bf16_flops"]
    t_bytes = gpt.attention_kernel_bytes(cfg, 16, 1024) \
        / peaks["hbm_bytes_per_s"]
    assert 4.4e-3 < t_bytes < 4.5e-3 < 4.7e-3 < t_ops < 4.8e-3
    assert gpt.kv_cache_bytes(cfg, 256, 1024) == 9_663_676_416
    assert abs(gpt.grad_allreduce_bytes(cfg) / 1e6 - 247.1) < 0.1


def test_resnet50_forward_ops_match_the_published_count():
    cfg = resnet.build(RESNET)
    # 4.09 G multiply-adds a 224x224 image (He et al. quote 3.8 G for the
    # v1 stride placement; v1.5 moves the stride to the 3x3 and costs more)
    assert abs(resnet.forward_flops_per_image(cfg) / 2e9 - 4.09) < 0.02
    assert resnet.input_bytes_per_image() == 602_112
    n_convs = sum(1 for _ in resnet._convs(cfg, 224))
    assert n_convs == 1 + 3 * 16 + 4     # stem, 16 bottlenecks, 4 projections
