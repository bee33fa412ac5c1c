"""The benchmark's FLOP functions against hand counts."""
import benchtest_util
from benchlib import cells


def test_lenet5_forward_is_the_hand_count():
    cfg, mod = cells.config("lenet5-fleet")
    # conv1 24x24x6 outputs x 25 taps; conv2 8x8x16 x (25 x 6); fc1 256x120;
    # fc2 120x84; head 84x10 -- multiply-adds, x 2
    conv1, conv2 = 172_800, 307_200
    fc1, fc2, head = 61_440, 20_160, 1_680
    assert mod.forward_flops(cfg) == conv1 + conv2 + fc1 + fc2 + head
    assert mod.forward_flops(cfg) == 563_280
    assert mod.train_flops_per_sample(cfg) == 3 * 563_280


def test_xlstm_forward_at_reduced_size_is_the_hand_count():
    cfg, mod = benchtest_util.small_config("xlstm-125m")
    cfg.update(d_model=8, num_heads=2, vocab_size=16, ssm_chunk=4,
               block_pattern=["mlstm", "slstm"], num_layers=2)
    # mLSTM, d=8, di=16, H=2, P=8, Q=4 (causal mean (Q+1)/2 = 2.5):
    #   w_up 2*8*32=512, q/k/v 3*2*16*16=1536, gates 2*16*4=128,
    #   down 2*16*8=256, scores 2*2*8*2.5=80, values 2*2*9*2.5=90,
    #   chunk states and read-out 2*2*2*9*8=576          -> 3178
    # sLSTM, d=8, H=2, P=4: gates 2*8*32=512, recurrence 2*4*2*4*4=256,
    #   up 2*8*16=256, down 2*8*8=128                     -> 1152
    # head 2*8*16=256
    assert mod.forward_flops_per_token(cfg) == 3178 + 1152 + 256
    # discriminator per step at T = K = 15 tokens and negatives:
    #   2*(2*T*d*V + 2*K*d*V) + 3*2*T*V*K = 2*(3840 + 3840) + 21600
    cfg.update(disc_tokens=15, num_negatives=15)
    disc = 2 * (3840 + 3840) + 21600
    assert mod.train_flops_per_token(cfg, 15) == 3 * 4586 + disc / 15


def test_xlstm_train_flops_at_full_size_are_the_hand_count():
    cfg, mod = cells.config("xlstm-125m")
    # mLSTM, d=768, di=1536, H=4, P=384, Q=256 (causal mean 128.5):
    #   w_up 4,718,592, q/k/v 14,155,776, gates 24,576, down 2,359,296,
    #   scores 394,752, values 395,780, chunk states and read-out
    #   2,365,440                                          -> 24,414,212
    # sLSTM, P=192: gates 4,718,592, recurrence 1,179,648, up 2,359,296,
    #   down 1,179,648                                     ->  9,437,184
    # head 2*768*50304 = 77,266,944; 10 mLSTM and 2 sLSTM blocks
    fwd = 10 * 24_414_212 + 2 * 9_437_184 + 77_266_944
    assert mod.forward_flops_per_token(cfg) == fwd == 340_283_432
    # discriminator over 4 x 2048 tokens, T = 8192, K = 1023, V = 50304:
    #   2*(2*T*d*V + 2*K*d*V) + 6*T*V*K = 3,953,440,456,704 FLOP a step
    disc = 2 * (632_970_805_248 + 79_044_083_712) + 2_529_410_678_784
    assert mod.train_flops_per_token(cfg, 4 * 2048) == 3 * fwd + disc / 8192
    assert mod.train_flops_per_token(cfg, 4 * 2048) == 1_503_448_008
