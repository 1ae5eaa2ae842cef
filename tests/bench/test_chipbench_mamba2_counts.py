"""The Mamba-2 cell's FLOP and byte counts, pinned at its shapes by hand."""
import json

import _chipbench_lm as lm
import _chipbench_tiny as tiny
from chipbench import mamba2_counts as m
from chipbench import registry

CFG = json.loads((tiny.BENCH / "configs" / "mamba2-370m.json").read_text())
RING2 = json.loads((tiny.BENCH / "traffic" / "ring2.json").read_text())


def test_parameters_by_hand():
    """d 1024, d_inner 2048, H 32, N 128, G 1, conv width 4, V 50,280:
    in_proj 1024 x (2*2048 + 2*128 + 32) = 1024 x 4384 = 4,489,216; the
    conv's 2304 channels, 4 weights and a bias each, 11,520; A_log, dt_bias
    and D 3 x 32 = 96; the gated norm 2048; out_proj 2048 x 1024 =
    2,097,152; the pre-norm 1024: 6,601,056 a block.  24 blocks
    158,425,344, the tied embedding 50,280 x 1024 = 51,486,720, the final
    norm 1024: 209,913,088 an agent."""
    assert m.layer_param_count(CFG) == 4_489_216 + 11_520 + 96 + 2048 + 2_097_152 + 1024
    assert m.layer_param_count(CFG) == 6_601_056
    assert m.param_count(CFG) == 24 * 6_601_056 + 51_486_720 + 1024 == 209_913_088


def test_program_layout_holds_the_counted_parameters():
    import jax

    fam = registry.load_module([tiny.BENCH], "families", "mamba2")
    for cfg in (CFG, lm.tiny_config()):
        shapes = fam.program_shapes(cfg, "float32")
        assert sum(s.size for s in jax.tree.leaves(shapes)) == m.param_count(cfg)


def test_ssd_counts_by_hand():
    """Per token and layer, chunk Q 256: C B^T 2 G Q N = 2*256*128 = 65,536;
    the mask and the masked product H Q (2P + 1) = 32*256*129 = 1,056,768;
    chunk states and state-to-output 4 H N P = 4*32*128*64 = 1,048,576;
    state passing 2 H N P / Q = 2,048: 2,172,928 forward.  Bytes in
    training, float32: inputs x, dt, B, C = 2048 + 32 + 256 = 2336 floats,
    y 2048, so 4 (3*2336 + 2*2048) = 44,416.  A round holds 2 agents x 3
    minibatches x 2 sequences x 1024 tokens = 12,288 tokens, through 24
    layers, and training is three forward passes."""
    assert m.ssd_forward_flops_per_token(CFG) == 65_536 + 1_056_768 + 1_048_576 + 2_048
    assert m.ssd_bytes_per_token(CFG) == 4 * (3 * 2336 + 2 * 2048) == 44_416
    assert m.sequences_per_round(RING2) == 12
    assert m.ssd_flops_per_round(CFG, RING2) == 3 * 12_288 * 24 * 2_172_928
    assert m.ssd_bytes_per_round(CFG, RING2) == 12_288 * 24 * 44_416 == 13_098_811_392


def test_round_counts_by_hand():
    """A block's forward work per token: in_proj 2*1024*4384 = 8,978,432,
    the conv 2*4*2304 = 18,432, the SSD 2,172,928, out_proj 2*2048*1024 =
    4,194,304: 15,364,096.  The head 2*1024*50,280 = 102,973,440 at the
    1023 positions of a sequence that predict a token.  Three times
    (12,288 x 24 x 15,364,096 + 12 x 1023 x 102,973,440) is 17.385 TFLOP a
    round, 1.415 GFLOP a token.  Bytes: the state 6 x 2 x 4 x 209,913,088 =
    10,075,828,224 and the windows 3 x 2 x 2 x (4*1024 + 4) = 49,200."""
    assert m.layer_forward_flops_per_token(CFG) == 15_364_096
    flops = 3 * (12_288 * 24 * 15_364_096 + 12 * 1023 * 102_973_440)
    assert m.train_flops_per_round(CFG, RING2) == flops == 17_385_474_686_976
    assert m.train_bytes_per_round(CFG, RING2) == 10_075_828_224 + 49_200


def test_family_counts_follow_the_config_and_traffic():
    fam = registry.load_module([tiny.BENCH], "families", "mamba2")
    for cfg, traffic in ((CFG, RING2), (lm.tiny_config(), lm.tiny_traffic())):
        assert fam.flops_per_round(cfg, traffic) == m.train_flops_per_round(cfg, traffic)
        assert fam.bytes_per_round(cfg, traffic) == m.train_bytes_per_round(cfg, traffic)
        assert fam.ssd_flops_per_round(cfg, traffic) == m.ssd_flops_per_round(cfg, traffic)
        assert fam.ssd_bytes_per_round(cfg, traffic) == m.ssd_bytes_per_round(cfg, traffic)
    doubled = dict(RING2, batch=4)
    assert fam.flops_per_round(CFG, doubled) == 2 * fam.flops_per_round(CFG, RING2)
