"""Multi-device semantics via subprocess (8 host devices; smoke tests keep 1)."""
import os
import subprocess
import sys

import pytest

SCRIPT = os.path.join(os.path.dirname(__file__), "md_check.py")

def _run(check: str, timeout: int = 900):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, SCRIPT, check], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"{check} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_hierarchical_equals_flat_psum():
    assert "OK" in _run("hier")


@pytest.mark.slow
def test_compressed_psum_close_to_exact():
    assert "OK" in _run("compressed")


@pytest.mark.slow
def test_moe_expert_parallel_multidevice():
    assert "OK" in _run("moe")


@pytest.mark.slow
def test_train_modes_multidevice():
    assert "OK" in _run("train")


@pytest.mark.slow
def test_mapreduce_device_sharded_multidevice():
    """Sharded device engine == host mesh oracle (bit-exact), 8 host devices:
    ragged tier counts, single-shard tiers, empty partitions, both shuffle
    index paths, and the traceable in-shard_map reduce."""
    assert "OK" in _run("mapreduce-device")


@pytest.mark.slow
def test_mapreduce_streaming_sharded_multidevice():
    """Split-streaming executor == monolithic on an 8-device data mesh
    (2/5/n-of-1 splits, identity+int16, wordcount combiner on/off/auto)."""
    assert "OK" in _run("mapreduce-streaming")


@pytest.mark.slow
def test_mapreduce_lanes_multidevice():
    """Per-device concurrent lanes across 8 host devices == monolithic,
    with and without injected chaos (delays, transient faults, clones)."""
    assert "OK" in _run("mapreduce-lanes")


@pytest.mark.slow
def test_mapreduce_cluster_shuffle_multidevice():
    """Four-chip cluster shuffle (4 host devices): per-chip map, one
    all-to-all to the partition owners, tiers born sharded. Bit-identical
    to the one-device path and the host engine; each chip's tier slices
    hold the one-device rows of its partitions, each in RA order; the
    exchange's byte counter equals the entries that left their home chip;
    the pair kernel's windows keep as few tile pairs as on one device."""
    assert "OK" in _run("mapreduce-cluster", timeout=300)
