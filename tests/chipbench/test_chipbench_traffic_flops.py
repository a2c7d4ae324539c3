"""The traffic generator's determinism and the FLOP and byte counts against
hand-worked numbers."""

import collections
import json
import os

import pytest

from chipbench import flops, traffic
from chipbench.cells import BENCH_ROOT

HERE = os.path.join(BENCH_ROOT, "chipbench")


def spec(name):
    return traffic.load(os.path.join(HERE, "traffic", f"{name}.json"))


def conf(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_same_seed_same_requests(seed):
    a = traffic.generate(spec("chat"), seed, 40, 32000)
    b = traffic.generate(spec("chat"), seed, 40, 32000)
    assert a == b and len(a) == 40  # whole epochs of gaps: the same work for every seed
    assert all(0 <= t < 32000 for r in a for t in r.prompt)


def test_seeds_differ_in_order_not_in_work():
    s = spec("chat")
    n = s["prompt_tokens"]["levels"] * 2
    by_seed = []
    for seed in (1, 2):
        reqs = []
        for r in traffic.stream(s, seed, 32000):
            reqs.append(r)
            if len(reqs) == n:
                break
        by_seed.append(reqs)
    lens = [collections.Counter(len(r.prompt) for r in reqs) for reqs in by_seed]
    assert lens[0] == lens[1]
    assert [len(r.prompt) for r in by_seed[0]] != [len(r.prompt) for r in by_seed[1]]
    assert by_seed[0][0].prompt != by_seed[1][0].prompt


def test_gap_levels_keep_the_rate():
    gaps = traffic.gap_levels(4.0, 64)
    assert sum(gaps) / len(gaps) == pytest.approx(0.25)
    assert min(gaps) > 0


def test_lengths_stay_inside_the_clip_and_warmup_covers_them():
    s = spec("chat")
    levels = traffic.length_levels(s["prompt_tokens"])
    assert min(levels) == s["prompt_tokens"]["min"] and max(levels) <= s["prompt_tokens"]["max"]
    sent = {len(r.prompt) for r in traffic.generate(s, 3, 30, 32000)}
    assert sent <= set(traffic.warmup_lengths(s))


@pytest.mark.parametrize("cell,sizes", [
    ("mistral7b-d16.chat", {4, 2, 1}),           # the mix names the sizes its arrivals can form
    ("mistral7b-d16.batch", {16, 8, 4, 2, 1}),   # every power of two up to the slots
])
def test_warm_up_groups_cover_every_length_at_every_size(cell, sizes):
    from chipbench import run
    from chipbench.cells import load_cell

    c = load_cell(cell)
    groups = run.warm_groups(c)
    assert {len(g) for g in groups} == sizes
    lengths = set(traffic.warmup_lengths(c.traffic))
    for size in sizes:
        assert {n for g in groups if len(g) == size for n, _ in g} == lengths
    assert groups[0][0][1] == 2 * c.config["engine"]["decode_block"]  # the first decodes through every block size


def test_closed_loop_stream_has_no_due_time():
    reqs = traffic.stream(spec("batch"), 5, 32000)
    assert [next(reqs).due_s for _ in range(3)] == [0.0, 0.0, 0.0]


def test_mistral_layer_by_hand():
    m = conf("mistral7b-d16")
    # q and o: 4096 x 4096 each; k and v: 4096 x 1024 each; three 4096 x 14336.
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert by_hand == 218_103_808 == flops.llm_layer_params(m)
    assert flops.llm_kv_bytes_per_token(m) == 2 * 16 * 8 * 128 * 2 == 65_536
    weights = 2 * (16 * 218_103_808 + 4096 * 32000 + 33 * 4096)
    assert flops.llm_weight_bytes(m) == weights == 7_241_736_192


def test_mistral_token_and_step_by_hand():
    m = conf("mistral7b-d16")
    body = 2 * 16 * 218_103_808
    head = 2 * 4096 * 32000
    assert flops.llm_token_flops(m, 0, with_head=False) == body + 4 * 16 * 4096 * 1
    assert flops.llm_token_flops(m, 299, with_head=True) == body + head + 4 * 16 * 4096 * 300
    f, b = flops.llm_decode_step(m, [300, 500])
    assert f == 2 * (body + head) + 4 * 16 * 4096 * (301 + 501)
    assert b == 7_241_736_192 + 65_536 * (301 + 1 + 501 + 1)
    # A request: the head once for the prompt, once for each later token.
    assert flops.llm_request_flops(m, 2, 2) == (
        2 * body + head + 4 * 16 * 4096 * (1 + 2) + body + head + 4 * 16 * 4096 * 3
    )


def test_sliding_window_caps_attention():
    m = dict(conf("mistral7b-d16"), sliding_window=8)
    assert flops.llm_token_flops(m, 100, False) == flops.llm_token_flops(m, 7, False)


def test_resnet50_by_hand():
    convs = flops.resnet_convs()
    assert len(convs) == 54  # 53 convolutions and the classifier
    assert convs[0] == (112, 112, 7, 7, 3, 64)
    stem = 2 * 112 * 112 * 7 * 7 * 3 * 64
    assert stem == 236_027_904
    # First bottleneck at 56 x 56: 64->64 1x1, 64->64 3x3, 64->256 1x1, 64->256 projection.
    first = 2 * 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    assert sum(2 * oh * ow * kh * kw * ci * co for oh, ow, kh, kw, ci, co in convs[1:5]) == first
    assert convs[-1] == (1, 1, 1, 1, 2048, 1000)
    fwd = flops.resnet_forward_flops()
    assert 8.1e9 < fwd < 8.3e9  # the usual "4.1 GMACs"
    assert flops.resnet_train_flops() == 3 * fwd - stem
