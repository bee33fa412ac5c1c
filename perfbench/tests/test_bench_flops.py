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
