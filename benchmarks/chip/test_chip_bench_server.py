"""Per-layer readers of the server's own counters, off the chip."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def _layer(name):
    return harness.load_module(os.path.join(HERE, "layers", name + ".py"),
                               "layer_" + name.replace(".", "_"))


@pytest.mark.parametrize("stats, share", [
    ({"slot_waits": 45, "batches": 50}, 90.0),
    ({"slot_waits": 0, "batches": 8}, 0.0),
    ({"slot_waits": 3, "batches": 3}, 100.0),
])
def test_slot_wait_share_reads_the_servers_counter(stats, share):
    read = _layer("serve.slot_wait_share").read
    assert read({"server_stats": stats}) == pytest.approx(share)


@pytest.mark.parametrize("ctx", [
    {},
    {"server_stats": None},
    {"server_stats": {"batches": 8}},                 # a server without it
    {"server_stats": {"slot_waits": 0, "batches": 0}},
])
def test_slot_wait_share_reads_nothing_without_launches_counted(ctx):
    assert _layer("serve.slot_wait_share").read(ctx) is None
