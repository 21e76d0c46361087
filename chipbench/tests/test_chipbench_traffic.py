"""The traffic generator: seeded, the same load for every seed."""
import numpy as np
import pytest

from chipbench import traffic
from chipbench.harness import ROOT

CLOSED = {"loop": "closed", "outstanding": 64, "buckets": [32],
          "max_wait_ms": 20, "pool": 64}


def test_same_seed_same_schedule():
    np.testing.assert_array_equal(traffic.image_order(CLOSED, 2**31 + 17, 100),
                                  traffic.image_order(CLOSED, 2**31 + 17, 100))


def test_other_seed_other_order_same_load():
    a = traffic.image_order(CLOSED, 1, 64)
    b = traffic.image_order(CLOSED, 2, 64)
    assert not np.array_equal(a, b)
    # The same images, in another order.
    assert sorted(a) == sorted(b)


def test_every_image_once_per_pass():
    order = traffic.image_order(CLOSED, 9, 3 * 64)
    for k in range(3):
        assert sorted(order[64 * k:64 * (k + 1)]) == list(range(64))


@pytest.mark.parametrize("path", sorted((ROOT / "traffic").glob("*.json"))
                         + sorted((ROOT / "testdata" / "traffic")
                                  .glob("*.json")), ids=lambda p: p.stem)
def test_mixes_load(path):
    mix = traffic.load(path)
    assert mix["pool"] > 0 and mix["buckets"]


def test_malformed_mix_refused(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"loop": "closed", "outstanding": 10, "buckets": [256],'
                   ' "max_wait_ms": 20, "pool": 8}')
    with pytest.raises(ValueError, match="outstanding"):
        traffic.load(bad)
    bad.write_text('{"loop": "closed", "outstanding": 600, "buckets": [256],'
                   ' "max_wait_ms": 20, "pool": 8, "rate": 3}')
    with pytest.raises(ValueError, match="unknown keys"):
        traffic.load(bad)
    bad.write_text('{"loop": "open", "rate_per_s": 5, "buckets": [1],'
                   ' "max_wait_ms": 20, "pool": 8}')
    with pytest.raises(ValueError, match="'loop' must be"):
        traffic.load(bad)
