"""Every seed of a traffic mix offers the same rows and tokens, with
other ids."""

import json
import os

import numpy as np
import pytest

from chipbench.traffic_kinds import pretrain

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")
SEEDS = (0, 1, 7, 2 ** 31 + 12345, 4_000_000_007)


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["pretrain-seq2048"])
def test_equal_totals_different_ids(name):
    params = mix(name)
    feeds = [pretrain.generate(params, s, 10.0, 50304) for s in SEEDS]
    first = feeds[0]
    for feed in feeds[1:]:
        assert feed.offered() == first.offered()
        assert feed(0)[0].shape == first(0)[0].shape
        assert not np.array_equal(feed(0)[0], first(0)[0])
    assert first.offered()["tokens_per_step"] == \
        params["batch"] * params["seq"]


def test_pretrain_rows_all_differ_and_repeat_by_seed():
    params = {"kind": "pretrain", "batch": 4, "seq": 64}
    feed = pretrain.generate(params, 2 ** 31 + 5, 10.0, 50304)
    ids, labels = feed(0)
    assert ids.shape == (4, 64) and ids.dtype == np.int32
    assert len({r.tobytes() for r in ids}) == 4
    assert not np.array_equal(feed(0)[0], feed(1)[0])
    again = pretrain.generate(params, 2 ** 31 + 5, 10.0, 50304)
    assert np.array_equal(again(3)[0], feed(3)[0])
    assert feed.offered()["tokens_per_step"] == 256
