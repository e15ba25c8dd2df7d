"""Work counts and peaks of the benchmark (bench/work.py), against hand
counts at published widths; and the traffic generator's fixed multiset
of sizes (bench/traffic.py)."""
import json
import os

import numpy as np
import pytest

from bench import traffic, work
from bench.sizes import Sizes, sizes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _sizes(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return sizes(json.load(f))


QWEN3 = _sizes("qwen3-30b-a3b")
# A model with MHA and a shared expert: Qwen1.5-MoE-A2.7B's published
# widths at 4 layers.  No cell serves it yet; the counts are held here
# for the configuration that brings one.
QWEN2 = Sizes(layers=4, d=2048, vocab=151936, heads=16, kv_heads=16,
              head_dim=128, experts=60, top_k=4, fe=1408, f_shared=5632,
              norm_topk=False, qk_norm=False, rope_theta=1e6, eps=1e-6)


def test_peaks_known_and_unknown_kind():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("s,d,fe", [(QWEN3, 2048, 768), (QWEN2, 2048, 1408)])
def test_expert_ffn_hand_count(s, d, fe):
    hist = [2, 0, 5, 1, 0]          # 3 slots lit, 8 (token, expert) pairs
    flops, nbytes = work.expert_ffn(s, hist)
    assert flops == 8 * (2 * d * fe * 2 + 2 * fe * d)     # up+gate, down
    assert nbytes == 3 * (d * 2 * fe + fe * d) * 2 + 8 * (d + d) * 2


@pytest.mark.parametrize("s,heads,kv", [(QWEN3, 32, 4), (QWEN2, 16, 16)])
def test_flash_decode_hand_count(s, heads, kv):
    flops, nbytes = work.flash_decode(s, [0, 99])     # 1 + 100 keys
    assert flops == 101 * heads * 128 * 2 * 2          # q.k and p.v
    assert nbytes == 101 * kv * 128 * 2 * 2 + 2 * (heads * 128 * 2) * 2


def test_step_flops_hand_count_qwen3():
    # one decode row writing position 9, and a 4-token chunk at 100..103
    d, v, L = 2048, 151936, 5
    proj = d * 32 * 128 + 2 * d * 4 * 128 + 32 * 128 * d
    per_tok = 2 * (proj + d * 128 + 8 * 3 * d * 768)
    attn_ctx = 10 + (101 + 102 + 103 + 104)
    want = L * (5 * per_tok + 4 * 32 * 128 * attn_ctx) + 2 * d * v
    assert work.step_flops(QWEN3, [(100, 4)], [9]) == want


def test_step_flops_counts_shared_expert_qwen2():
    d = 2048
    one = work.step_flops(QWEN2, [], [0])
    proj = d * 16 * 128 * 2 + 2 * d * 16 * 128
    per_tok = 2 * (proj + d * 60 + 4 * 3 * d * 1408 + 3 * d * 5632)
    assert one == 4 * (per_tok + 4 * 16 * 128) + 2 * d * 151936


def test_min_seconds_is_the_larger_bound():
    pk = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.min_seconds(2e12, 1e9, pk) == 2.0
    assert work.min_seconds(1e12, 3e9, pk) == 3.0


MIX = {"loop": "open", "rate_per_s": 5.0,
       "prompt": {"mean": 512, "sigma": 0.6, "min": 64, "max": 2048},
       "output": {"mean": 256, "sigma": 0.5, "min": 16, "max": 1024}}


def test_traffic_same_work_every_seed():
    """Seeds reorder one multiset of lengths and gaps; the window holds
    rate * seconds requests, all due inside it."""
    a = traffic.generate(MIX, 7, 30.0, 1000)
    b = traffic.generate(MIX, 2 ** 33 + 5, 30.0, 1000)
    assert len(a) == len(b) == 150
    for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(f, a)) == sorted(map(f, b))
        assert list(map(f, a)) != list(map(f, b))
    due = np.array([r.due for r in a])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 30.0
    again = traffic.generate(MIX, 7, 30.0, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))


def test_traffic_lengths_follow_the_mix():
    lens = traffic.quantile_lengths(1000, MIX["prompt"])
    assert lens.min() >= 64 and lens.max() <= 2048
    assert abs(np.median(lens) - 512 * np.exp(-0.18)) < 10


def test_static_batches_same_work_every_seed():
    """A static batch's prompts are a function of the seed and the
    batch's index: one shape for every seed, other tokens."""
    from bench import batch
    with open(os.path.join(ROOT, "bench", "traffic", "batch.json")) as f:
        mix = json.load(f)
    a = batch.prompts(mix, 7, 0, 1000)
    assert a.shape == (mix["batch"], mix["prompt"]) and a.dtype == np.int32
    assert np.array_equal(a, batch.prompts(mix, 7, 0, 1000))
    for other in (batch.prompts(mix, 7, 1, 1000),
                  batch.prompts(mix, 2 ** 33 + 5, 0, 1000)):
        assert other.shape == a.shape and not np.array_equal(other, a)
        assert 0 <= other.min() and other.max() < 1000


def test_static_sample_is_drawn_from_the_seed():
    """The check's rows: distinct (batch, row) pairs of the finished
    batches, the same for one seed, others for another."""
    from bench import batch
    sent = [batch.Batch(i, [np.zeros(32)], []) for i in range(3)]
    a = batch.sample(sent, 11, 8)
    assert len(set(a)) == 8 and a == batch.sample(sent, 11, 8)
    assert all(0 <= i < 3 and 0 <= r < 32 for i, r in a)
    assert a != batch.sample(sent, 12, 8)
