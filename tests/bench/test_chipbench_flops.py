"""bench/flops.py against hand counts at a SMOKE size, and the roofline
reader's bound.  CPU only."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, harness  # noqa: E402
from bench.trace import Trace  # noqa: E402

# qwen3-8b's SMOKE widths: 2 layers, d 64, 4 query and 2 KV heads of 16,
# d_ff 128, vocabulary 512
C = {"L": 2, "d": 64, "H": 4, "Hkv": 2, "hd": 16, "ff": 128, "V": 512}


def test_matmul_parameters():
    # wq 64x64, wk and wv 64x32, wo 64x64, gate, up 64x128, down 128x64
    assert flops.layer_matmul_params(C) == 4096 + 2 * 2048 + 4096 + 3 * 8192
    assert flops.matmul_params(C, head=False) == 2 * 36864
    assert flops.matmul_params(C, head=True) == 2 * 36864 + 64 * 512


def test_qft_step_by_hand():
    # per sequence of 32: teacher fwd 2N, student fwd 2N + bwd 4N per token;
    # attention fwd 2 layers x 4 x 4 heads x 16 x (1 + ... + 32) keys, and
    # 4 of those (teacher, student, student backward twice)
    matmul = 8 * 73728 * 32
    attn = 4 * (2 * 4 * 4 * 16 * 528)
    assert flops.qft_step(C, 2, 32, 0.0) == 2 * (matmul + attn)
    # with logits in the loss the lm_head counts too
    assert (flops.qft_step(C, 2, 32, 0.5) - flops.qft_step(C, 2, 32, 0.0)
            == 2 * 8 * 64 * 512 * 32)


def test_serving_counts_by_hand():
    assert flops.decode_token(C, 10) == 2 * (73728 + 32768) + 2 * 4 * 4 * 16 * 10
    # 4 tokens after 8 cached: keys 9 + 10 + 11 + 12; one row of logits
    assert flops.prefill_chunk(C, 8, 4) == (2 * 73728 * 4 + 2 * 64 * 512
                                            + 2 * 4 * 4 * 16 * 42)


def test_decode_attention_counts_live_lengths():
    ops, nbytes = flops.decode_attention(C, [3, 5])
    assert ops == 2 * 4 * 4 * 16 * 8
    # K and V int8 rows of the 8 live positions, per layer; q and out f32
    # and the two per-head scales, per slot and layer
    assert nbytes == 2 * (2 * 2 * 16 * 8 + 2 * (2 * 4 * 16 * 4 + 2 * 2 * 4))
    # a padded view (what a kernel may read) would count more: the count is
    # of what the algorithm needs
    assert flops.decode_attention(C, [4096, 4096])[1] > nbytes


@pytest.mark.parametrize("slack", [1.0, 1.5, 3.0])
def test_roofline_share_cannot_pass_100(slack):
    """Kernel time at or above the least time the chip could take for the
    live work reads at most 100%; only a count of work the program did not
    do could push it over."""
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    lengths = [1, 700, 4096, 33]
    ops, nbytes = flops.decode_attention(C, lengths)
    least = max(ops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    t_ns = least * slack * 1e9
    tr = Trace({0: {"ops": [["decode_attention.1", 0, t_ns]], "modules": []}},
               [["bench:window", 0, t_ns]])
    read = harness.load_metric_reader(ROOT, "decode_attn_roofline.chat")
    share = read({"trace": tr, "peaks": peaks, "flops": flops,
                  "counts": {"dims": C, "decode_lengths": lengths}})
    assert share == pytest.approx(100.0 / slack)
    assert share <= 100.0 + 1e-9
