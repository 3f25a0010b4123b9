"""The benchmark's work counts against hand counts."""

import json
import os

import pytest

from bench import work
from bench.peaks import peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def granite():
    with open(os.path.join(ROOT, "bench", "configs", "granite-moe-1b-a400m.json")) as f:
        return json.load(f)


@pytest.mark.parametrize(
    "name,n,block,flops,nbytes",
    [
        ("vectorAdd", 50_000, 256, 50_000, 600_000),
        ("warpPrefixStats", 1 << 20, 256, 26 << 20, 8 << 20),
        ("histogram64", 1 << 20, 256, 1 << 20, (4 << 20) + 512),
        ("gridReduce", 1 << 20, 128, (1 << 20) + 8192, (4 << 20) + 8 * 8192 + 4),
    ],
)
def test_cox_program_work(name, n, block, flops, nbytes):
    assert work.cox_program(name, n, block) == (flops, nbytes)


def test_unknown_program_has_no_work_model():
    with pytest.raises(KeyError):
        work.cox_program("transpose", 1024, 256)


def test_granite_active_params():
    # attention 3,145,728 + router 32,768 + 8 experts 12,582,912 per layer,
    # times 24, plus the tied head 1024 x 49,155
    assert work.granite_active_params(granite()) == 24 * 15_761_408 + 50_334_720


def test_granite_token_flops_grow_with_the_cache():
    cfg = granite()
    base = 2.0 * work.granite_active_params(cfg)
    assert work.granite_token_flops(cfg, 100) == base + 24 * 4.0 * 100 * 1024


def test_decode_attention_bytes():
    flops, nbytes = work.decode_attention(granite(), 100)
    # K and V: 100 positions x 8 heads x 64 x 2 B each; q and out 16 x 64 x 2 B
    assert nbytes == 24 * (2 * 100 * 8 * 64 * 2 + 2 * 16 * 64 * 2)
    assert flops == 24 * 4.0 * 100 * 16 * 64


def test_roofline_takes_the_larger_bound():
    p = peaks("TPU v5 lite")
    assert work.roofline_seconds(197e12, 1.0, p) == pytest.approx(1.0)
    assert work.roofline_seconds(1.0, 819e9, p) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks("TPU v9000")
